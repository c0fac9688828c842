"""Resident run server: a crash-safe multi-tenant simulation service.

The batch CLI pays the AOT compile price on every invocation; the
server pays it once.  `shadow1-tpu serve` turns the process into a
resident service that warms the standard shape buckets in the
background, accepts scenario requests over a local Unix socket
(protocol.py), schedules them for warm-graph affinity (requests whose
shape hint matches the last-executed one run first, so consecutive
requests hit the already-compiled graph), and runs every request under
the existing supervision stack: per-request data directory
(``DATA/runs/<id>/``), checkpointing, watchdog, the invariant sentinel,
and the full degradation ladder (supervise.Supervisor).

Crash safety is write-ahead: every lifecycle transition is appended and
fsync'd to ``DATA/server/journal.jsonl`` BEFORE the client sees the
acknowledgement, and each request's full record is mirrored atomically
to ``DATA/runs/<id>/request.json``.  A SIGKILL'd server therefore
loses nothing: a restart with ``serve --auto-resume`` folds the
journal, re-admits every queued / running / parked request, and each
re-admitted run auto-resumes from its newest checkpoint -- bitwise
identical to an uninterrupted run (the same trim-and-append contract
single-run --auto-resume already keeps; tools/faultdrill.py's `server`
drill SIGKILLs a loaded server and byte-compares every windows.jsonl
against solo references).

Admission control is loud: a full queue is refused with rc 2 naming
the current depth and the --queue-limit knob; a per-request --timeout
that expires (queued or mid-run) is refused with rc 2 naming
--timeout.  SIGTERM drains: stop admitting, ask every in-flight run to
checkpoint and park at its next launch boundary, journal the park, and
exit 0 -- parked runs re-enter the queue on the next --auto-resume
start.  Exit codes ride supervise.py's unified table end-to-end: the
rc a run would exit the CLI with is the rc `submit --wait` /
`status --wait` exits with.

Observability (Servescope; docs/observability.md "Servescope"): every
request finishes with ``runs/<id>/request_metrics.json`` (queue-wait,
affinity hit/miss, compile count + wall, device-step and host-drain
wall, ``host_drain_overlap_pct``, events/s, park/resume/recovery
counts) assembled from a per-request host-side Profiler
(``sync=False, counters=False`` -- the state pytree is untouched, so a
served run stays byte-identical to an unobserved one); a server-wide
counter registry (`ServerMetrics`) is snapshotted atomically to
``server/metrics.json`` on a cadence and served live by the ``stats``
protocol op; and every lifecycle transition appends one span row to
``server/schedule.jsonl``, which is REGENERATED from the journal on
every start -- the journal is ground truth, so the scheduler trace
survives a SIGKILL with no lost transitions.

See docs/robustness.md "Run server".
"""

from __future__ import annotations

import collections
import glob as glob_mod
import json
import os
import queue as queue_mod
import socket
import sys
import threading
import time
import traceback

from . import protocol
from .core.simtime import SIMTIME_ONE_SECOND
from .supervise import RC_FAILED, RC_INVARIANT, RC_OK, RC_USAGE

SEC = SIMTIME_ONE_SECOND

JOURNAL_VERSION = 1

# Spec keys that determine the compiled graph's ShapeKey for a config
# request (world size and blocks, never seeds or stop times): the
# scheduler's warm-graph affinity hint.  Builder requests hash the
# builder name plus its shape-determining kwargs the same way.
_SHAPE_SPEC_KEYS = (
    "config", "sock_slots", "pool_slab", "tcp_congestion_control",
    "interface_qdisc", "pcap", "pcap_ring", "log_level", "log_ring",
    "bucket", "devices", "scope", "trace_packets", "flight_rows",
    "digest_every", "digest_rows", "profile", "worlds", "sweep")


def _shape_hint(kind: str, spec: dict) -> str:
    if kind == "config":
        return json.dumps({k: spec.get(k) for k in _SHAPE_SPEC_KEYS},
                          sort_keys=True)
    if kind == "builder":
        kw = dict(spec.get("kwargs") or {})
        # Seeds and stop times change the trajectory, never the shapes.
        kw.pop("seed", None)
        kw.pop("stop_time", None)
        return json.dumps({"builder": spec.get("name"), **kw},
                          sort_keys=True)
    return "replay"


class RunControl:
    """The server's handle into a running request: `request("park")` /
    `request("cancel")` is polled by the run loop at launch boundaries
    (cli.run_config / sim._run_checkpointed), and a per-request
    deadline surfaces as a polled "timeout".  The loop records how it
    stopped in `outcome` ("parked" | "cancelled" | "timed_out")."""

    def __init__(self, deadline: float | None = None):
        self._lock = threading.Lock()
        self._action = None
        self.deadline = deadline  # time.monotonic() value, or None
        self.outcome = None

    def request(self, action: str) -> None:
        with self._lock:
            # cancel outranks park outranks nothing; never downgrade.
            if self._action != "cancel":
                self._action = action

    def poll(self) -> str | None:
        with self._lock:
            act = self._action
        if act is not None:
            return act
        if self.deadline is not None and time.monotonic() >= self.deadline:
            return "timeout"
        return None


class Request:
    """One submitted scenario: spec, lifecycle state, and its evidence
    trail.  Mutation happens under the server lock; `record()` is the
    JSON view status reports and request.json mirrors."""

    def __init__(self, rid: str, kind: str, spec: dict,
                 timeout: float | None = None,
                 submitted: float | None = None):
        self.id = rid
        self.kind = kind
        self.spec = spec
        self.timeout = float(timeout) if timeout else None
        self.submitted = submitted if submitted is not None else time.time()
        self.state = protocol.QUEUED
        self.rc = None
        self.trail = ["submitted"]
        self.restarts = 0        # server lives that re-admitted this run
        self.error = None
        self.crash = None        # {"path": ..., "class": ...}
        self.summary = None
        self.shape_hint = _shape_hint(kind, spec)
        self.control = None      # RunControl while running
        self.subscribers = []    # list[queue.Queue] of live streams
        # Servescope scheduler stamps (per-request accounting).
        self.enqueued_at = self.submitted  # when it last entered the queue
        self.queue_wait = 0.0    # accumulated queued seconds, ALL lives
        self.started = None      # wall time the last execution started
        self.finished = None     # wall time the run settled
        self.worker = None       # worker index that picked it
        self.affinity_hit = None  # shape hint matched the warm graph
        self.pick_reason = None  # "affinity" (jumped FIFO) | "fifo"
        self.parks = 0           # server-drain parks taken
        self.resumes = 0         # checkpoint resumes (emit "resumed")
        self.recoveries = 0      # ladder rungs taken (emit "recovered")
        self.quarantines = 0     # worlds quarantined (emit "quarantined")
        self.profiler = None     # per-request trace.Profiler while running

    def queue_wait_s(self) -> float:
        """Accumulated queue-wait over every server life, plus the
        wait-so-far when the request is still queued."""
        w = self.queue_wait
        if self.state == protocol.QUEUED and self.enqueued_at is not None:
            w += max(0.0, time.time() - self.enqueued_at)
        return round(w, 6)

    def record(self, run_dir: str) -> dict:
        return {
            "id": self.id, "kind": self.kind, "state": self.state,
            "rc": self.rc, "dir": run_dir, "spec": self.spec,
            "timeout": self.timeout, "submitted": self.submitted,
            "restarts": self.restarts, "trail": list(self.trail),
            "error": self.error, "crash": self.crash,
            "summary": self.summary,
            "shape_hint": self.shape_hint,
            "queue_wait_s": self.queue_wait_s(),
        }


class ServerMetrics:
    """Server-wide counter registry (Servescope tentpole 2): requests
    by state/kind/rc, queue high-water, per-worker busy time, affinity
    hit rate, journal fsync count + latency, recovery/readmit counts,
    and a recent-completions ring.  All mutation is under one small
    lock; `snapshot()` returns a JSON-able view the stats op and the
    server/metrics.json cadence writer share.  Host-side bookkeeping
    only -- nothing here touches a run's state pytree."""

    RECENT = 16

    def __init__(self, workers: int):
        self._lock = threading.Lock()
        self.t0 = time.time()
        self.submitted = 0
        self.by_state = {}       # terminal outcomes: state -> count
        self.by_kind = {}        # admissions: kind -> count
        self.by_rc = {}          # terminal outcomes: rc -> count
        self.readmitted = 0
        self.parked = 0
        self.resumes = 0
        self.recoveries = 0
        self.quarantines = 0
        self.affinity_hits = 0
        self.affinity_misses = 0
        self.queue_high_water = 0
        self.journal_events = 0
        self.fsyncs = 0
        self.fsync_s = 0.0
        self.workers = [{"busy_s": 0.0, "runs": 0, "current": None,
                         "since": None} for _ in range(workers)]
        self.recent = collections.deque(maxlen=self.RECENT)

    def submit(self, kind: str, depth: int) -> None:
        with self._lock:
            self.submitted += 1
            self.by_kind[kind] = self.by_kind.get(kind, 0) + 1
            self.queue_high_water = max(self.queue_high_water, depth)

    def pick(self, hit: bool) -> None:
        with self._lock:
            if hit:
                self.affinity_hits += 1
            else:
                self.affinity_misses += 1

    def journal(self, fsync_s: float) -> None:
        with self._lock:
            self.journal_events += 1
            self.fsyncs += 1
            self.fsync_s += fsync_s

    def worker_start(self, i: int, rid: str) -> None:
        with self._lock:
            w = self.workers[i]
            w["current"], w["since"] = rid, time.time()

    def worker_done(self, i: int) -> None:
        with self._lock:
            w = self.workers[i]
            if w["since"] is not None:
                w["busy_s"] += time.time() - w["since"]
            w["runs"] += 1
            w["current"], w["since"] = None, None

    def event(self, name: str, n: int = 1) -> None:
        """Bump a named lifecycle counter (readmitted / parked /
        resumes / recoveries)."""
        with self._lock:
            setattr(self, name, getattr(self, name) + n)

    def settle(self, req: "Request") -> None:
        """Account one terminal outcome and ring-buffer it."""
        with self._lock:
            self.by_state[req.state] = self.by_state.get(req.state, 0) + 1
            key = str(req.rc)
            self.by_rc[key] = self.by_rc.get(key, 0) + 1
            wall = None
            if req.started is not None and req.finished is not None:
                wall = round(req.finished - req.started, 3)
            self.recent.append({
                "id": req.id, "kind": req.kind, "state": req.state,
                "rc": req.rc, "wall_s": wall,
                "queue_wait_s": req.queue_wait_s(),
                "affinity_hit": req.affinity_hit})

    def snapshot(self) -> dict:
        now = time.time()
        with self._lock:
            hits, misses = self.affinity_hits, self.affinity_misses
            picks = hits + misses
            return {
                "uptime_s": round(now - self.t0, 3),
                "requests": {
                    "submitted": self.submitted,
                    "by_state": dict(self.by_state),
                    "by_kind": dict(self.by_kind),
                    "by_rc": dict(self.by_rc)},
                "affinity": {
                    "hits": hits, "misses": misses,
                    "hit_rate": round(hits / picks, 4) if picks else None},
                "journal": {
                    "events": self.journal_events,
                    "fsyncs": self.fsyncs,
                    "fsync_ms_total": round(self.fsync_s * 1e3, 3),
                    "fsync_ms_mean": round(
                        self.fsync_s / self.fsyncs * 1e3, 3)
                    if self.fsyncs else None},
                "workers": [{
                    "id": i, "busy_s": round(w["busy_s"], 3),
                    "runs": w["runs"], "current": w["current"],
                    "busy_for_s": round(now - w["since"], 3)
                    if w["since"] is not None else None}
                    for i, w in enumerate(self.workers)],
                "recovery": {
                    "readmitted": self.readmitted,
                    "parked": self.parked,
                    "resumes": self.resumes,
                    "recoveries": self.recoveries,
                    "quarantines": self.quarantines},
                "recent": list(self.recent),
            }


class Server:
    """The resident service.  `start()` recovers the journal, binds the
    socket, and launches the accept + worker threads; `wait()` blocks
    until `shutdown()` (a protocol shutdown op, SIGTERM, or a test)
    completes.  Everything is in-process and thread-based: requests
    run on worker threads inside this process, sharing the warmed
    compile cache -- the whole point of residency."""

    def __init__(self, data_dir: str, *, queue_limit: int = 8,
                 workers: int = 1, checkpoint_every: float = 2.0,
                 watchdog: float | None = None, auto_resume: bool = False,
                 metrics_every: float = 2.0, quiet: bool = True,
                 max_lanes: int = 4):
        self.data_dir = data_dir
        self.sdir = os.path.join(data_dir, "server")
        self.runs_dir = os.path.join(data_dir, "runs")
        self.sock_path = protocol.default_socket(data_dir)
        self.queue_limit = int(queue_limit)
        self.workers = max(1, int(workers))
        self.max_lanes = max(1, int(max_lanes))
        self.checkpoint_every = float(checkpoint_every)
        self.watchdog = watchdog
        self.auto_resume = bool(auto_resume)
        self.metrics_every = float(metrics_every)
        self.quiet = quiet
        self.warmed = None       # shapes.warm_buckets records, if warmed
        self.metrics = ServerMetrics(self.workers)

        self._lock = threading.RLock()
        self._cond = threading.Condition(self._lock)
        self._reqs: dict[str, Request] = {}
        self._queue: list[str] = []
        self._last_hint = None
        self._counter = 1
        self._draining = False
        self._stopping = False
        self._done = threading.Event()
        self._journal = None
        self._schedule = None    # server/schedule.jsonl live handle
        self._listener = None
        self._worker_threads = []
        self._readmitted = []

    # -- lifecycle --------------------------------------------------------

    def start(self) -> "Server":
        os.makedirs(self.sdir, exist_ok=True)
        os.makedirs(self.runs_dir, exist_ok=True)
        self._recover()
        jpath = os.path.join(self.sdir, "journal.jsonl")
        self._journal = open(jpath, "a", encoding="utf-8")
        # schedule.jsonl is DERIVED: regenerate it from the fsync'd
        # journal on every start, so a SIGKILL never loses a scheduler
        # transition, then keep the handle open for live appends.
        self._schedule = open(os.path.join(self.sdir, "schedule.jsonl"),
                              "w", encoding="utf-8")
        if os.path.exists(jpath):
            with open(jpath, encoding="utf-8") as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        ev = json.loads(line)
                    except json.JSONDecodeError:
                        continue  # torn tail line from a killed writer
                    self._append_schedule(ev)
        for req in self._readmitted:
            if req.state == protocol.QUEUED:
                # Journal the re-admission so a second crash still
                # counts every restart in the trail.  Stranded (parked,
                # no --auto-resume) requests are only re-mirrored.
                self._log({"ev": "readmit", "id": req.id,
                           "t": req.enqueued_at})
            self._sync_request(req)
        self._readmitted = []
        if self._readmit_count:
            self.metrics.event("readmitted", self._readmit_count)

        # A stale socket file from a killed server blocks bind(); it is
        # only stale if nobody answers on it.
        if os.path.exists(self.sock_path):
            try:
                protocol.request(self.sock_path, {"op": "ping"},
                                 timeout=1.0)
                raise RuntimeError(
                    f"a run server is already listening on "
                    f"{self.sock_path}")
            except protocol.ServerUnavailable:
                os.unlink(self.sock_path)
        s = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        s.bind(self.sock_path)
        s.listen(64)
        self._listener = s
        t = threading.Thread(target=self._accept_loop, daemon=True,
                             name="shadow1-serve-accept")
        t.start()
        for i in range(self.workers):
            wt = threading.Thread(target=self._worker_loop, args=(i,),
                                  daemon=True,
                                  name=f"shadow1-serve-worker-{i}")
            wt.start()
            self._worker_threads.append(wt)
        self._write_metrics_snapshot()
        threading.Thread(target=self._metrics_loop, daemon=True,
                         name="shadow1-serve-metrics").start()
        self._say(f"serve: listening on {self.sock_path} "
                  f"(queue-limit {self.queue_limit}, "
                  f"workers {self.workers}"
                  + (f", re-admitted {self._readmit_count} run(s)"
                     if self._readmit_count else "") + ")")
        return self

    def wait(self) -> None:
        self._done.wait()

    def shutdown(self, drain: bool = True) -> None:
        """Stop the service.  `drain=True` (the SIGTERM path) asks every
        in-flight run to checkpoint and park at its next launch
        boundary; `drain=False` cancels them.  Queued requests stay
        journaled as queued either way and re-admit on the next
        --auto-resume start."""
        with self._lock:
            if self._done.is_set() or self._draining:
                return
            self._draining = True
            running = [r for r in self._reqs.values()
                       if r.state == protocol.RUNNING
                       and r.control is not None]
        if running:
            self._say(f"serve: {'parking' if drain else 'cancelling'} "
                      f"{len(running)} in-flight run(s)")
        for r in running:
            r.control.request("park" if drain else "cancel")
        # Wait for the workers to park/cancel their current request.
        while True:
            with self._lock:
                if not any(r.state == protocol.RUNNING
                           for r in self._reqs.values()):
                    break
            time.sleep(0.05)
        with self._cond:
            self._stopping = True
            self._cond.notify_all()
            self._log({"ev": "drain", "parked": [r.id for r in running],
                       "t": time.time()})
        try:
            self._listener.close()
        except OSError:
            pass
        try:
            os.unlink(self.sock_path)
        except OSError:
            pass
        for t in self._worker_threads:
            t.join(timeout=10)
        self._write_metrics_snapshot()
        with self._lock:
            self._journal.close()
            if self._schedule is not None:
                self._schedule.close()
                self._schedule = None
        self._say("serve: stopped")
        self._done.set()

    # -- journal + recovery ----------------------------------------------

    def _log(self, ev: dict) -> None:
        """Write-ahead append: the line is on disk (fsync) before any
        caller-visible effect of the event."""
        with self._lock:
            self._journal.write(json.dumps(ev, sort_keys=True) + "\n")
            self._journal.flush()
            t0 = time.perf_counter()
            os.fsync(self._journal.fileno())
            self.metrics.journal(time.perf_counter() - t0)
            self._append_schedule(ev)

    _SCHEDULE_STATE = {
        "submit": protocol.QUEUED, "start": protocol.RUNNING,
        "park": protocol.PARKED, "cancel": protocol.CANCELLED,
        "readmit": protocol.QUEUED}

    def _schedule_row(self, ev: dict) -> dict | None:
        """Map one journal event to one schedule.jsonl span row: the
        lifecycle transition plus the scheduler context (shape hint,
        worker id, affinity hit, pick reason, queue depth at pick)."""
        name = ev.get("ev")
        if name == "drain":
            return {"t": ev.get("t"), "ev": "drain", "id": None,
                    "parked": ev.get("parked")}
        rid = ev.get("id")
        if rid is None or (name not in self._SCHEDULE_STATE
                           and name != "finish"):
            return None
        state = ev.get("state") if name == "finish" \
            else self._SCHEDULE_STATE[name]
        row = {"t": ev.get("t"), "ev": name, "id": rid, "state": state}
        req = self._reqs.get(rid)
        if req is not None:
            row["kind"] = req.kind
            row["shape_hint"] = req.shape_hint
        for k in ("worker", "hit", "reason", "depth", "rc"):
            if k in ev:
                row[k] = ev[k]
        return row

    def _append_schedule(self, ev: dict) -> None:
        """Append the schedule row for a journal event (call under the
        lock).  flush but no fsync: the journal is ground truth and the
        whole file is regenerated from it on start."""
        if self._schedule is None:
            return
        row = self._schedule_row(ev)
        if row is None:
            return
        self._schedule.write(json.dumps(row, sort_keys=True) + "\n")
        self._schedule.flush()

    _readmit_count = 0

    def _recover(self) -> None:
        """Fold the journal into request records.  Non-terminal requests
        (queued, running, parked) re-enter the queue under
        --auto-resume; without it they are parked in place with a loud
        trail note so `status` explains how to finish them."""
        path = os.path.join(self.sdir, "journal.jsonl")
        if not os.path.exists(path):
            return
        with open(path, encoding="utf-8") as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    ev = json.loads(line)
                except json.JSONDecodeError:
                    continue  # torn tail line from a killed writer
                self._fold(ev)
        readmit = [r for r in self._reqs.values()
                   if r.state not in protocol.TERMINAL]
        for req in sorted(readmit, key=lambda r: r.id):
            was = req.state
            if self.auto_resume:
                req.restarts += 1
                req.trail.append(
                    f"readmitted (was {was} when the server stopped)")
                req.state = protocol.QUEUED
                # Queue-wait accumulates across server lives: close the
                # open queued segment (includes the dead-server gap --
                # the client was waiting the whole time) and start a new
                # one at re-admission.
                now = time.time()
                if req.enqueued_at is not None:
                    req.queue_wait += max(0.0, now - req.enqueued_at)
                req.enqueued_at = now
                self._queue.append(req.id)
                self._readmitted.append(req)
            else:
                req.trail.append(
                    f"stranded {was} by a server stop; restart with "
                    f"`serve --auto-resume` to re-admit it")
                req.state = protocol.PARKED
                self._readmitted.append(req)  # re-journal + re-mirror
        self._readmit_count = len(self._queue)

    def _fold(self, ev: dict) -> None:
        t = ev.get("ev")
        rid = ev.get("id")
        if t == "submit":
            req = Request(rid, ev.get("kind"), ev.get("spec") or {},
                          timeout=ev.get("timeout"),
                          submitted=ev.get("t"))
            self._reqs[rid] = req
            n = self._id_num(rid)
            if n is not None and n >= self._counter:
                self._counter = n + 1
            return
        req = self._reqs.get(rid) if rid else None
        if req is None:
            return
        if t == "start":
            req.state = protocol.RUNNING
            req.trail.append("started")
            ts = ev.get("t")
            if ts is not None:
                if req.enqueued_at is not None:
                    req.queue_wait += max(0.0, ts - req.enqueued_at)
                req.enqueued_at = None
                req.started = ts
            req.worker = ev.get("worker", req.worker)
            if "hit" in ev:
                req.affinity_hit = ev["hit"]
            if "reason" in ev:
                req.pick_reason = ev["reason"]
        elif t == "finish":
            req.state = ev.get("state", protocol.FAILED)
            req.rc = ev.get("rc")
            req.trail.append(f"finished rc {req.rc}")
            req.finished = ev.get("t")
            # A queued-timeout refusal finishes without a start: the
            # open queued segment still counts as wait.
            if req.finished is not None and req.enqueued_at is not None:
                req.queue_wait += max(
                    0.0, req.finished - req.enqueued_at)
            req.enqueued_at = None
        elif t == "park":
            req.state = protocol.PARKED
            req.trail.append("parked (server drain)")
            req.parks += 1
            req.enqueued_at = None
        elif t == "cancel":
            req.state = protocol.CANCELLED
            req.rc = RC_FAILED
            req.trail.append("cancelled")
            req.finished = ev.get("t")
            if req.finished is not None and req.enqueued_at is not None:
                req.queue_wait += max(
                    0.0, req.finished - req.enqueued_at)
            req.enqueued_at = None
        elif t == "readmit":
            req.restarts += 1
            req.state = protocol.QUEUED
            req.trail.append("readmitted")
            ts = ev.get("t")
            if ts is not None:
                if req.enqueued_at is not None:
                    req.queue_wait += max(0.0, ts - req.enqueued_at)
                req.enqueued_at = ts

    @staticmethod
    def _id_num(rid):
        try:
            return int(str(rid).lstrip("r"))
        except ValueError:
            return None

    def _sync_request(self, req: Request) -> None:
        """Mirror the full record atomically to runs/<id>/request.json
        (tmp + rename -- never torn, like every other state file).  The
        connection handler and the worker both mirror a request, so the
        write and the rename happen under the lock: unlocked, one
        thread's rename moved the other's tmp away, and the worker died
        on the FileNotFoundError with the request never settled."""
        d = os.path.join(self.runs_dir, req.id)
        os.makedirs(d, exist_ok=True)
        path = os.path.join(d, "request.json")
        tmp = path + ".tmp"
        with self._lock:
            rec = req.record(d)
            with open(tmp, "w") as f:
                json.dump(rec, f, indent=1, sort_keys=True)
            os.replace(tmp, path)

    # -- socket side ------------------------------------------------------

    def _accept_loop(self):
        while True:
            try:
                conn, _ = self._listener.accept()
            except OSError:
                return  # listener closed: shutting down
            threading.Thread(target=self._handle, args=(conn,),
                             daemon=True,
                             name="shadow1-serve-conn").start()

    def _handle(self, conn):
        rf = conn.makefile("r", encoding="utf-8")
        wf = conn.makefile("w", encoding="utf-8")
        try:
            msg = protocol.recv(rf)
            if msg is None:
                return
            op = msg.get("op")
            if op == "ping":
                with self._lock:
                    protocol.send(wf, {
                        "ok": True,
                        "version": protocol.PROTOCOL_VERSION,
                        "pid": os.getpid(),
                        "queue_depth": len(self._queue),
                        "queue_limit": self.queue_limit,
                        "draining": self._draining,
                        "warmed": bool(self.warmed)})
            elif op == "submit":
                self._op_submit(msg, wf)
            elif op == "status":
                self._op_status(msg, wf)
            elif op == "stats":
                protocol.send(wf, {"ok": True, "stats": self._stats()})
            elif op == "cancel":
                self._op_cancel(msg, wf)
            elif op == "shutdown":
                protocol.send(wf, {"ok": True})
                threading.Thread(
                    target=self.shutdown,
                    kwargs={"drain": bool(msg.get("drain", True))},
                    daemon=True).start()
            else:
                protocol.send(wf, {"ok": False, "rc": RC_USAGE,
                                   "error": f"unknown op {op!r}"})
        except (BrokenPipeError, ConnectionResetError, OSError,
                json.JSONDecodeError, ValueError):
            pass  # client went away or spoke garbage; drop the stream
        finally:
            try:
                conn.close()
            except OSError:
                pass

    def _op_submit(self, msg, wf):
        kind = msg.get("kind")
        spec = msg.get("spec") or {}
        sub = None
        with self._lock:
            err = self._admission_error(kind, spec)
            if err is not None:
                protocol.send(wf, {"ok": False, "rc": RC_USAGE,
                                   "error": err})
                return
            rid = f"r{self._counter:04d}"
            self._counter += 1
            req = Request(rid, kind, spec, timeout=msg.get("timeout"))
            # Write-ahead: the submit is durable BEFORE the client sees
            # the id, so an ack'd request survives any kill.
            self._log({"ev": "submit", "id": rid, "kind": kind,
                       "spec": spec, "timeout": req.timeout,
                       "t": req.submitted})
            self._reqs[rid] = req
            self._queue.append(rid)
            self.metrics.submit(kind, len(self._queue))
            if msg.get("wait"):
                sub = queue_mod.Queue()
                req.subscribers.append(sub)
            self._cond.notify_all()
        self._sync_request(req)
        protocol.send(wf, {"ok": True, "id": rid})
        if sub is not None:
            self._pump(req, sub, wf,
                       progress=bool(msg.get("progress", True)))

    def _admission_error(self, kind, spec):
        """Admission control (call under the lock): loud rc-2 refusals
        that name the knob, per docs/robustness.md."""
        if self._draining or self._stopping:
            return ("server is draining (SIGTERM received): not "
                    "admitting new requests; in-flight runs are being "
                    "checkpointed and parked")
        if len(self._queue) >= self.queue_limit:
            return (f"queue full: {len(self._queue)} queued request(s) "
                    f"at --queue-limit {self.queue_limit}; retry later "
                    f"or restart the server with a higher --queue-limit")
        if kind == "config":
            cfg = spec.get("config")
            if not cfg or not os.path.exists(cfg):
                return (f"config {cfg!r} not found on the server's "
                        f"filesystem (paths are resolved server-side)")
            return None
        if kind == "builder":
            from . import sim
            name = spec.get("name")
            if not name or getattr(sim, f"build_{name}", None) is None:
                return (f"unknown world builder {name!r} (known: the "
                        f"sim.build_* family)")
            if not isinstance(spec.get("kwargs", {}), dict):
                return "builder kwargs must be a JSON object"
            return None
        if kind == "replay":
            target = spec.get("run") or ""
            tdir = target if os.path.isdir(target) \
                else os.path.join(self.runs_dir, target)
            if not os.path.isdir(tdir):
                return (f"replay target {target!r} is neither a run id "
                        f"under {self.runs_dir} nor a data directory")
            return None
        return (f"unknown request kind {kind!r} (expected 'config', "
                f"'builder', or 'replay')")

    def _op_status(self, msg, wf):
        rid = msg.get("id")
        if rid is None:
            with self._lock:
                snap = {
                    "ok": True,
                    "server": {
                        "version": protocol.PROTOCOL_VERSION,
                        "pid": os.getpid(),
                        "data_dir": self.data_dir,
                        "queue_depth": len(self._queue),
                        "queue_limit": self.queue_limit,
                        "workers": self.workers,
                        "draining": self._draining,
                        "warmed": bool(self.warmed)},
                    "runs": [self._record_locked(r)
                             for _, r in sorted(self._reqs.items())]}
            protocol.send(wf, snap)
            return
        sub = None
        with self._lock:
            req = self._reqs.get(rid)
            if req is None:
                protocol.send(wf, {"ok": False, "rc": RC_USAGE,
                                   "error": f"unknown run id {rid!r}"})
                return
            rec = self._record_locked(req)
            wait = bool(msg.get("wait"))
            if wait and req.state in (protocol.QUEUED, protocol.RUNNING):
                sub = queue_mod.Queue()
                req.subscribers.append(sub)
        protocol.send(wf, {"ok": True, "run": rec})
        if sub is not None:
            self._pump(req, sub, wf, progress=True)
        elif msg.get("wait"):
            # Already settled: synthesize the terminal event.
            if req.state == protocol.PARKED:
                protocol.send(wf, {"event": "parked", "id": rid})
            else:
                protocol.send(wf, {"event": "done", "id": rid,
                                   "rc": req.rc, "state": req.state,
                                   "crash": req.crash,
                                   "error": req.error,
                                   "summary": req.summary})

    def _record_locked(self, req: Request) -> dict:
        """record() plus the live queue position (call under the lock):
        a queued request's status names where it sits in line."""
        rec = req.record(os.path.join(self.runs_dir, req.id))
        if req.state == protocol.QUEUED and req.id in self._queue:
            rec["queue_position"] = self._queue.index(req.id)
        return rec

    def _op_cancel(self, msg, wf):
        rid = msg.get("id")
        with self._lock:
            req = self._reqs.get(rid)
            if req is None:
                protocol.send(wf, {"ok": False, "rc": RC_USAGE,
                                   "error": f"unknown run id {rid!r}"})
                return
            if req.state == protocol.QUEUED:
                self._queue.remove(rid)
                req.state = protocol.CANCELLED
                req.rc = RC_FAILED
                req.trail.append("cancelled")
                now = time.time()
                req.finished = now
                if req.enqueued_at is not None:
                    req.queue_wait += max(0.0, now - req.enqueued_at)
                    req.enqueued_at = None
                self._log({"ev": "cancel", "id": rid, "t": now})
                self.metrics.settle(req)
                done = {"event": "done", "id": rid, "rc": RC_FAILED,
                        "state": protocol.CANCELLED}
                subs = list(req.subscribers)
                resp = {"ok": True, "id": rid,
                        "state": protocol.CANCELLED}
            elif req.state == protocol.RUNNING:
                req.control.request("cancel")
                done, subs = None, []
                resp = {"ok": True, "id": rid, "state": "cancelling"}
            else:
                done, subs = None, []
                resp = {"ok": True, "id": rid, "state": req.state,
                        "note": "already settled"}
        for q in subs:
            q.put(done)
        if done is not None:
            self._write_request_metrics(req)
        self._sync_request(req)
        protocol.send(wf, resp)

    def _pump(self, req, sub, wf, progress=True):
        """Relay a request's event stream to one client until its
        terminal event; the connection closing mid-stream just drops
        the subscription (the run itself is unaffected)."""
        try:
            while True:
                try:
                    ev = sub.get(timeout=1.0)
                except queue_mod.Empty:
                    if self._done.is_set():
                        return
                    continue
                if ev.get("event") == "progress" and not progress:
                    continue
                protocol.send(wf, ev)
                if ev.get("event") in ("done", "parked"):
                    return
        except (BrokenPipeError, ConnectionResetError, OSError):
            pass
        finally:
            with self._lock:
                if sub in req.subscribers:
                    req.subscribers.remove(sub)

    def _emit(self, req, ev: dict) -> None:
        with self._lock:
            subs = list(req.subscribers)
        for q in subs:
            q.put(ev)

    # -- scheduler + workers ---------------------------------------------

    def _worker_loop(self, widx: int):
        while True:
            with self._cond:
                while (not self._queue or self._draining) \
                        and not self._stopping:
                    self._cond.wait(0.25)
                if self._stopping:
                    return
                batch = self._pick_batch_locked(widx)
                if not batch:
                    continue
            self.metrics.worker_start(widx, batch[0].id)
            try:
                if len(batch) == 1:
                    self._execute(batch[0])
                else:
                    self._execute_batch(widx, batch)
            finally:
                self.metrics.worker_done(widx)

    def _pick_locked(self, worker: int):
        """Warm-graph affinity: prefer the oldest queued request whose
        shape hint matches the last-executed one (it reuses the
        compiled graph); fall back to FIFO.  Stamps the pick on the
        request: worker id, affinity hit/miss, and whether affinity
        (not queue order) made the choice."""
        if self._draining or not self._queue:
            return None
        idx = 0
        if self._last_hint is not None:
            for i, rid in enumerate(self._queue):
                if self._reqs[rid].shape_hint == self._last_hint:
                    idx = i
                    break
        rid = self._queue.pop(idx)
        req = self._reqs[rid]
        req.worker = worker
        req.affinity_hit = (self._last_hint is not None
                            and req.shape_hint == self._last_hint)
        req.pick_reason = "affinity" if (req.affinity_hit and idx > 0) \
            else "fifo"
        self._last_hint = req.shape_hint
        self.metrics.pick(req.affinity_hit)
        return req

    def _batchable(self, req) -> bool:
        """A request the lane train can carry: a builder world with
        none of the per-request instrumentation/layout knobs that
        change the state pytree or need a solo run loop (devices,
        bucket, scope, lineage, digests)."""
        if req.kind != "builder":
            return False
        spec = req.spec
        return not any(spec.get(k) for k in
                       ("devices", "bucket", "scope", "trace_packets",
                        "digest_every"))

    def _claim_batchable_locked(self, hint, worker, n) -> list:
        """Pop up to n queued batchable requests whose shape hint
        matches `hint` (they share the train's compiled graph by
        construction).  Caller holds the lock."""
        out = []
        i = 0
        while i < len(self._queue) and len(out) < n:
            r = self._reqs[self._queue[i]]
            if self._batchable(r) and r.shape_hint == hint:
                self._queue.pop(i)
                r.worker = worker
                r.affinity_hit = True
                r.pick_reason = "batched"
                self.metrics.pick(True)
                out.append(r)
            else:
                i += 1
        return out

    def _pick_batch_locked(self, worker: int) -> list:
        """One scheduling decision: the affinity/FIFO pick, plus -- when
        it is batchable and compatible peers are queued -- up to
        max_lanes-1 of them, co-batched onto one lane train
        (docs/robustness.md "Continuous batching").  A lone batchable
        request still runs solo (the solo compiled graph stays warm
        for affinity); trains form when >= 2 compatible requests are
        queued together, and accept later joiners mid-flight."""
        req = self._pick_locked(worker)
        if req is None:
            return []
        batch = [req]
        if self.max_lanes > 1 and self._batchable(req):
            batch += self._claim_batchable_locked(
                req.shape_hint, worker, self.max_lanes - 1)
        return batch

    def _begin_exec(self, req: Request):
        """Move a picked request into RUNNING: close its queued
        segment, refuse it if it timed out while queued (returns
        None), then stamp control/profiler/journal and return
        (run_dir, emit) -- the per-request evidence-harvesting emit
        closure shared by the solo and batched paths."""
        from . import trace
        now = time.time()
        with self._lock:
            # Close the open queued segment: the request is off the
            # queue whether it runs or is refused below.
            if req.enqueued_at is not None:
                req.queue_wait += max(0.0, now - req.enqueued_at)
                req.enqueued_at = None
        if req.timeout and now - req.submitted >= req.timeout:
            self._finish(req, RC_USAGE, error=(
                f"request {req.id} spent {now - req.submitted:.1f}s "
                f"queued, past its --timeout {req.timeout:g}s; raise "
                f"--timeout or submit to a less loaded server"))
            return None
        deadline = None
        if req.timeout:
            deadline = time.monotonic() + (req.timeout
                                           - (now - req.submitted))
        run_dir = os.path.join(self.runs_dir, req.id)
        os.makedirs(run_dir, exist_ok=True)
        with self._lock:
            req.control = RunControl(deadline)
            req.state = protocol.RUNNING
            req.started = now
            req.trail.append("started")
            # counters=False: per-request accounting must stay host-side
            # only -- a served run's state pytree (and so its
            # trajectory) is byte-identical to an unobserved one.
            req.profiler = trace.Profiler(sync=False, counters=False)
            self._log({"ev": "start", "id": req.id, "t": now,
                       "worker": req.worker, "hit": req.affinity_hit,
                       "reason": req.pick_reason,
                       "depth": len(self._queue)})
        self._sync_request(req)
        self._emit(req, {"event": "state", "id": req.id,
                         "state": protocol.RUNNING})

        def emit(ev):
            # Harvest evidence off the stream before relaying it.
            if ev.get("event") == "summary":
                req.summary = ev.get("summary")
            elif ev.get("event") == "crash":
                crash = ev.get("crash") or {}
                req.crash = {
                    "path": ev.get("path")
                    or os.path.join(run_dir, "crash.json"),
                    "class": crash.get("failure", {}).get("class")}
            elif ev.get("event") == "resumed":
                req.resumes += 1
                self.metrics.event("resumes")
            elif ev.get("event") == "recovered":
                req.recoveries += 1
                self.metrics.event("recoveries")
            elif ev.get("event") == "quarantined":
                # Ensemble request: world(s) frozen by the quarantine
                # rung while the survivors keep running.
                n = len(ev.get("worlds") or ()) or 1
                req.quarantines += n
                self.metrics.event("quarantines", n)
            self._emit(req, ev)

        return run_dir, emit

    def _settle_exec(self, req: Request, rc: int) -> None:
        """Map a finished execution onto the request's terminal (or
        parked) state -- the shared tail of the solo and batched
        paths.  The control outcome outranks rc: park re-journals for
        the next --auto-resume life, cancel/timeout carry their own
        exit codes."""
        outcome = req.control.outcome
        if outcome == "parked":
            with self._lock:
                req.state = protocol.PARKED
                req.parks += 1
                req.trail.append("parked (server drain)")
                self._log({"ev": "park", "id": req.id,
                           "t": time.time()})
            self.metrics.event("parked")
            self._sync_request(req)
            self._emit(req, {"event": "parked", "id": req.id})
        elif outcome == "cancelled":
            self._finish(req, RC_FAILED, state=protocol.CANCELLED,
                         error=f"request {req.id} cancelled")
        elif outcome == "timed_out":
            self._finish(req, RC_USAGE, error=(
                f"request {req.id} exceeded its --timeout "
                f"{req.timeout:g}s and was stopped at a launch "
                f"boundary; raise --timeout for longer scenarios"))
        else:
            self._finish(req, rc)

    def _execute(self, req: Request) -> None:
        from . import trace
        begun = self._begin_exec(req)
        if begun is None:
            return
        run_dir, emit = begun
        try:
            rc = self._dispatch(req, run_dir, req.control, emit)
        except BaseException as e:  # noqa: BLE001 -- worker must survive
            req.error = f"{type(e).__name__}: {e}"
            if not self.quiet:
                traceback.print_exc()
            rc = RC_FAILED
        finally:
            # The run loop installs req.profiler process-globally; drop
            # it so later requests (or the warm thread) can't attribute
            # their compiles to a finished request.  Best-effort under
            # workers>1 -- the install slot is global by design.
            if trace.current() is req.profiler:
                trace.install(None)
        self._settle_exec(req, rc)

    def _begin_lane(self, req: Request):
        """_begin_exec + batch.prepare for one train member; maps
        preparation failures (bad builder name/kwargs) onto the same
        exit codes _dispatch would give them.  Returns the prepared
        batch.Lane, or None when the request settled already."""
        from . import batch as batch_mod
        begun = self._begin_exec(req)
        if begun is None:
            return None
        run_dir, emit = begun
        try:
            return batch_mod.prepare(
                req, run_dir, req.control, emit,
                default_ck_s=self.checkpoint_every)
        except (ValueError, FileNotFoundError, KeyError, TypeError,
                AttributeError, json.JSONDecodeError) as e:
            req.error = f"{type(e).__name__}: {e}"
            self._settle_exec(req, RC_USAGE)
            return None
        except BaseException as e:  # noqa: BLE001 -- worker must survive
            req.error = f"{type(e).__name__}: {e}"
            if not self.quiet:
                traceback.print_exc()
            self._settle_exec(req, RC_FAILED)
            return None

    def _execute_batch(self, widx: int, reqs: list) -> None:
        """Run co-picked compatible requests as ONE lane train
        (batch.LaneTrain): each request is a lane of a live vmapped
        ensemble, advancing on its own solo launch grid through one
        compiled graph, with per-request checkpoints/windows.jsonl/
        metrics byte-identical to solo runs.  Queued compatible
        requests join free lanes at launch boundaries; each lane
        settles the moment it retires."""
        from . import batch as batch_mod
        from . import trace
        hint = reqs[0].shape_hint
        lanes = [ln for ln in (self._begin_lane(r) for r in reqs)
                 if ln is not None]
        if not lanes:
            return

        def claim_more(n):
            with self._lock:
                if self._draining or self._stopping:
                    return []
                claimed = self._claim_batchable_locked(hint, widx, n)
            return [ln for ln in (self._begin_lane(r) for r in claimed)
                    if ln is not None]

        def on_retire(lane):
            if not lane.settled:
                lane.settled = True
                self._settle_exec(lane.req, lane.rc
                                  if lane.rc is not None else RC_FAILED)

        # Compiles during the train attribute to the primary request's
        # profiler; per-lane spans/drains go to each request's own.
        trace.install(lanes[0].req.profiler)
        train = batch_mod.LaneTrain(self.max_lanes,
                                    claim_more=claim_more,
                                    on_retire=on_retire)
        try:
            train.run(lanes)
        except BaseException as e:  # noqa: BLE001 -- worker must survive
            if not self.quiet:
                traceback.print_exc()
            train.abort(f"{type(e).__name__}: {e}")
            for lane in train.lanes:
                if not lane.settled:
                    lane.settled = True
                    self._settle_exec(lane.req, RC_FAILED)
        finally:
            if trace.current() is lanes[0].req.profiler:
                trace.install(None)

    def _dispatch(self, req, run_dir, control, emit) -> int:
        from .cli import CliError
        try:
            if req.kind == "config":
                return self._run_config_kind(req, run_dir, control, emit)
            if req.kind == "builder":
                return self._run_builder_kind(req, run_dir, control,
                                              emit)
            if req.kind == "replay":
                return self._run_replay_kind(req, run_dir)
            req.error = f"unknown request kind {req.kind!r}"
            return RC_USAGE
        except CliError as e:
            req.error = str(e)
            return e.rc
        except (ValueError, FileNotFoundError, KeyError, TypeError,
                json.JSONDecodeError) as e:
            req.error = f"{type(e).__name__}: {e}"
            return RC_USAGE

    def _run_config_kind(self, req, run_dir, control, emit) -> int:
        from . import cli
        spec = dict(req.spec)
        # Re-parse for a fully-defaulted namespace, then lay the spec
        # over it: the client sent exactly the run-flag set, so an
        # older client simply inherits the server's defaults.
        ns = cli._parser().parse_args(["run", spec.get("config") or "?"])
        for k, v in spec.items():
            if hasattr(ns, k):
                setattr(ns, k, v)
        # Server-side overrides: per-request data directory, always
        # supervised + auto-resumable (the crash-safety contract), the
        # server's cadence/watchdog defaults when the request set none.
        ns.data_directory = run_dir
        ns.quiet = True
        ns.auto_resume = True
        if not getattr(ns, "checkpoint_every", None):
            ns.checkpoint_every = self.checkpoint_every
        if getattr(ns, "watchdog", None) is None:
            ns.watchdog = self.watchdog
        ns.progress = bool(spec.get("progress"))
        return cli.run_config(ns, control=control, emit=emit,
                              profiler=req.profiler)

    def _run_builder_kind(self, req, run_dir, control, emit) -> int:
        from . import sim
        from .supervise import UnrecoveredFailure
        spec = req.spec
        name = spec["name"]
        kwargs = dict(spec.get("kwargs") or {})
        ck_s = float(spec.get("checkpoint_every")
                     or self.checkpoint_every)
        wd = spec.get("watchdog", self.watchdog)
        devices = spec.get("devices")
        state, params, app = getattr(sim, f"build_{name}")(**kwargs)
        try:
            state = sim.run(
                state, params, app,
                devices=devices, bucket=bool(spec.get("bucket")),
                scope=spec.get("scope"),
                lineage=spec.get("trace_packets"),
                digest=spec.get("digest_every"),
                checkpoint_every=int(ck_s * SEC),
                checkpoint_dir=run_dir,
                checkpoint_world=(name, kwargs),
                supervise={"watchdog_s": wd, "quiet": True},
                profiler=req.profiler,
                control=control, emit=emit, resume=True)
        except UnrecoveredFailure as e:
            req.error = str(e)
            req.crash = {"path": e.path,
                         "class": e.crash.get("failure", {}).get("class")}
            return e.rc
        if control.outcome is not None:
            return RC_OK  # _execute maps the outcome, not this rc
        import jax.numpy as jnp
        req.summary = {
            "simulated_seconds": int(state.now) / SEC,
            "windows": int(state.n_windows),
            "packets_sent": int(jnp.sum(state.hosts.pkts_sent)),
            "err_flags": int(state.err)}
        emit({"event": "summary", "summary": req.summary})
        return RC_OK if int(state.err) == 0 else RC_INVARIANT

    def _run_replay_kind(self, req, run_dir) -> int:
        from . import replay as replay_mod
        from .trace import ReplayDivergence
        spec = req.spec
        target = spec.get("run") or ""
        tdir = target if os.path.isdir(target) \
            else os.path.join(self.runs_dir, target)
        try:
            summary = replay_mod.replay(
                tdir, window=spec.get("window"),
                out_dir=os.path.join(run_dir, "replay"), quiet=True)
        except ReplayDivergence as e:
            req.error = str(e)
            req.summary = {"replay_diverged": {
                "window": e.window, "fields": e.fields}}
            return RC_INVARIANT
        req.summary = summary
        sn = summary.get("sentinel")
        if sn and sn.get("violations"):
            req.error = (f"replay reproduced a sentinel violation "
                         f"({'+'.join(sn['classes'])}) at window "
                         f"{sn['first_bad_window']}")
            return RC_INVARIANT
        return RC_OK

    def _finish(self, req, rc, state=None, error=None) -> None:
        with self._lock:
            req.rc = int(rc)
            req.state = state or (protocol.DONE if rc == RC_OK
                                  else protocol.FAILED)
            req.finished = time.time()
            if req.enqueued_at is not None:
                # Settled without ever starting (queued refusal).
                req.queue_wait += max(0.0,
                                      req.finished - req.enqueued_at)
                req.enqueued_at = None
            if error:
                req.error = error
            req.trail.append(f"finished rc {req.rc}")
            if req.crash is None:
                p = os.path.join(self.runs_dir, req.id, "crash.json")
                if os.path.exists(p):
                    req.crash = {"path": p, "class": None}
            self._log({"ev": "finish", "id": req.id, "rc": req.rc,
                       "state": req.state, "t": req.finished})
            self.metrics.settle(req)
        self._write_request_metrics(req)
        self._sync_request(req)
        done = {"event": "done", "id": req.id, "rc": req.rc,
                "state": req.state}
        if req.error:
            done["error"] = req.error
        if req.crash:
            done["crash"] = req.crash
        if req.summary is not None:
            done["summary"] = req.summary
        self._emit(req, done)

    # -- servescope: per-request + fleet metrics --------------------------

    def _write_request_metrics(self, req: Request) -> None:
        """Assemble runs/<id>/request_metrics.json from the scheduler
        stamps plus the per-request Profiler, atomically (tmp +
        rename).  Called once per terminal transition; a re-admitted
        run overwrites it at its real finish with the accumulated
        queue-wait / restart counts."""
        from . import trace
        run_dir = os.path.join(self.runs_dir, req.id)
        os.makedirs(run_dir, exist_ok=True)
        prof = req.profiler
        m = prof.metrics() if prof is not None else {}
        phases = m.get("phases") or {}

        def phase_ms(names):
            return round(sum((phases.get(n) or {}).get("total_s", 0.0)
                             for n in names) * 1e3, 3)

        events = (m.get("device_counters") or {}).get("events")
        wall = None
        if req.started is not None and req.finished is not None:
            wall = round(req.finished - req.started, 6)
        out = {
            "id": req.id, "kind": req.kind, "state": req.state,
            "rc": req.rc, "shape_hint": req.shape_hint,
            "worker": req.worker,
            "queue_wait_s": round(req.queue_wait, 6),
            "affinity_hit": req.affinity_hit,
            "pick_reason": req.pick_reason,
            "wall_s": wall,
            "compiles": m.get("compiles", 0),
            "compile_ms": m.get("compile_ms", 0.0),
            # Pipelined runs record dispatch->ready walls as
            # device_window spans (the engine's per-chunk device_step
            # spans are dispatch-only blips); prefer them when present.
            "device_step_ms": phase_ms(("device_window",))
            or phase_ms(("device_step",)),
            "drain_ms": phase_ms(trace._HOST_DRAIN_PHASES),
            "host_drain_overlap_pct": m.get("host_drain_overlap_pct",
                                            0.0),
            "events": events,
            "events_per_s": round(events / wall, 3)
            if events is not None and wall else None,
            "checkpoints": len(glob_mod.glob(
                os.path.join(run_dir, "ckpt", "win_*.npz"))),
            "parks": req.parks,
            "resumes": req.resumes,
            "recoveries": req.recoveries,
            "quarantines": req.quarantines,
            "n_worlds": (req.summary or {}).get("n_worlds")
            if isinstance(req.summary, dict) else None,
            "restarts": req.restarts,
            "submitted": req.submitted,
            "started": req.started,
            "finished": req.finished,
        }
        path = os.path.join(run_dir, "request_metrics.json")
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(out, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
        # Builder runs have no CLI end-block to write the trace; drop
        # one here so tools/plot.py can merge it with schedule.jsonl
        # (config runs already wrote theirs via cli.run_config).
        tpath = os.path.join(run_dir, "trace.json")
        if prof is not None and prof.events \
                and not os.path.exists(tpath):
            try:
                prof.write_trace(tpath)
            except OSError:
                pass

    def _stats(self) -> dict:
        """One fleet snapshot: the ServerMetrics counters plus the live
        queue / worker / warm view.  Serves the `stats` protocol op and
        the server/metrics.json cadence writer."""
        with self._lock:
            queue_ids = list(self._queue)
            states = {}
            for r in self._reqs.values():
                states[r.state] = states.get(r.state, 0) + 1
            queued = [{
                "id": rid, "position": i,
                "shape_hint": self._reqs[rid].shape_hint,
                "queue_wait_s": self._reqs[rid].queue_wait_s()}
                for i, rid in enumerate(queue_ids)]
            draining = self._draining
            warmed = self.warmed
            last_hint = self._last_hint
        snap = self.metrics.snapshot()
        snap.update({
            "ts": time.time(),
            "version": protocol.PROTOCOL_VERSION,
            "pid": os.getpid(),
            "data_dir": self.data_dir,
            "draining": draining,
            "states": states,
            "queue": {"depth": len(queue_ids),
                      "limit": self.queue_limit,
                      "high_water": self.metrics.queue_high_water,
                      "queued": queued},
            "warm": {"warmed": bool(warmed),
                     "buckets": len(warmed) if warmed else 0,
                     "last_hint": last_hint},
        })
        return snap

    def _write_metrics_snapshot(self) -> None:
        """Atomically snapshot `_stats()` to server/metrics.json (tmp +
        rename, like every other state file)."""
        path = os.path.join(self.sdir, "metrics.json")
        tmp = path + ".tmp"
        try:
            with open(tmp, "w") as f:
                json.dump(self._stats(), f, indent=1, sort_keys=True)
            os.replace(tmp, path)
        except OSError:
            pass  # metrics are best-effort; never take the server down

    def _metrics_loop(self) -> None:
        while not self._done.wait(self.metrics_every):
            self._write_metrics_snapshot()

    def _say(self, msg):
        if not self.quiet:
            print(f"[shadow1-tpu] {msg}", file=sys.stderr)


def serve(args) -> int:
    """`shadow1-tpu serve`: run the resident server until SIGTERM /
    SIGINT / a protocol shutdown.  Exit code 0 on a clean drain."""
    import signal

    srv = Server(args.data_directory,
                 queue_limit=args.queue_limit,
                 workers=args.workers,
                 checkpoint_every=args.checkpoint_every,
                 watchdog=args.watchdog,
                 auto_resume=args.auto_resume,
                 quiet=args.quiet,
                 max_lanes=getattr(args, "max_lanes", 4))
    try:
        srv.start()
    except (OSError, RuntimeError) as e:
        print(f"error: {e}", file=sys.stderr)
        return RC_USAGE

    def _term(signum, frame):
        threading.Thread(target=srv.shutdown, kwargs={"drain": True},
                         daemon=True, name="shadow1-serve-drain").start()

    signal.signal(signal.SIGTERM, _term)
    signal.signal(signal.SIGINT, _term)

    if not args.no_warm:
        # AOT-warm the standard bucket set once, off the accept path:
        # requests admitted during the warm just compile on first use
        # exactly as the batch CLI would.
        def _warm():
            try:
                from . import shapes
                srv.warmed = shapes.warm_buckets(
                    buckets=args.warm_buckets,
                    apps=tuple(args.warm_apps))
                if not args.quiet:
                    print(f"[shadow1-tpu] serve: warmed "
                          f"{len(srv.warmed)} bucket graph(s)",
                          file=sys.stderr)
            except Exception as e:  # noqa: BLE001 -- warm is best-effort
                print(f"[shadow1-tpu] serve: bucket warm failed ({e}); "
                      f"requests will compile on first use",
                      file=sys.stderr)

        threading.Thread(target=_warm, daemon=True,
                         name="shadow1-serve-warm").start()
    srv.wait()
    return RC_OK
