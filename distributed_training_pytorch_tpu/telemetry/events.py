"""Structured JSONL event log — the run's flight recorder.

The framework's narrative observability so far lived in free-text log lines
(``utils/logger.py``); answering "why did the loss spike at step 12k?" or
"how many preemptions did this run survive?" meant regexing a logfile. The
event log records the run's *discrete* happenings — run start/end,
compilation, checkpoint save/restore, preemption, fault injection,
loss-scale backoff, anomaly (including ``kind="memory_growth"``, the
live-memory leak detector — the "memory anomaly"), profiling captures
(``profile_capture``: trace path, traced window, category fractions +
dispatch-gap audit, emitted by ``profiling.StepTraceCapture``), perf-gate
verdicts (``perf_gate``: measured vs baseline, tolerance, verdict, emitted
by ``scripts/perf_gate.py``), static-audit verdicts (``static_audit``:
per-rule lint counts, waiver counts, undonated param/opt-state bytes of
the single-step and chained programs, precision leaks, host callbacks,
per-mesh comm bytes + comm-audit findings and gate verdicts,
emitted by ``scripts/static_audit.py --events``), memory-preflight
verdicts (``memory_preflight``: predicted peak vs capacity, per-class
attribution, batch/microbatch/fsdp recommendations, emitted by
``memory.preflight.run_preflight`` before the first dispatch), and
resharding restores (``checkpoint_reshard``: a checkpoint whose recorded
sharding layout differs from the restore target's — mesh axes and sharded
leaf counts on both sides, emitted by ``CheckpointManager.restore``; the
DP<->FSDP elasticity path of docs/parallelism.md), and elastic restores
(``elastic_restore``: a resume that crossed a device-count change — old/new
mesh axes and device counts, old/new grad-accumulation factors, the re-plan
reason, and whether the mesh was re-planned or explicitly overridden,
emitted by the Trainer after a topology-changed restore; the N!=M elastic
path of docs/fault_tolerance.md), and run-doctor verdicts (``run_doctor``:
the ranked bottleneck diagnosis — top verdict, per-verdict severity
scores, steady-state goodput fractions — emitted by
``scripts/run_doctor.py --events``; the ``anomaly`` kind vocabulary also
includes ``straggler``, the slowest-chip-ratio detector of
``telemetry/straggler.py``), and the A/B layer's records (ISSUE 14:
``run_compare`` — an across-runs comparison's kind, clean verdict, step_ms
delta, ranked attribution rows and provenance-mismatch keys, emitted by
``scripts/run_compare.py --events``; ``bench_history`` — the
committed-rounds ledger's flat streaks and regressions, emitted by
``scripts/bench_history.py --events``), and the live-operations layer's
records (ISSUE 15: ``heartbeat`` — the liveness pulse, emitted by the
trainer at the existing ``log_every`` syncs (``source="loop"``: epoch,
``step_in_epoch``, ``units`` executed this attempt, ``step_ms``,
``live_bytes`` where sampled, and the cumulative ``goodput_seconds``
snapshot) and from the step watchdog's patrol thread between syncs
(``source="watchdog"``, plus ``since_progress_s`` — seconds since the
last completed execution unit), debounced to
``Telemetry(heartbeat_every_s=...)``; ``monitor_alert`` — a debounced
alert-rule firing from the streaming monitor (``telemetry/monitor.py``:
``rule``, ``run_dir``, ``status``, measured ``value`` vs ``threshold``,
``message``, emitted by ``scripts/run_monitor.py --events``), and the
closed-loop layer's record (ISSUE 16: ``controller_action`` — one
remediation decision by the fleet controller (``telemetry/controller.py``
via ``scripts/fleet_controller.py``): the ``action`` taken (``restart`` |
``restart_excluding`` | ``tune`` | ``keep`` | ``revert`` | ``give_up`` |
``refuse``),
the ``run_dir`` and ``attempt`` acted on, the triggering ``reason``
verdict/rule, the justifying ``evidence`` rows copied from the doctor
verdict or alert that fired, and budget state (``restarts_used`` /
``max_restarts``, ``backoff_s``); the ``fault_injection`` kind vocabulary
also gains ``slow_chip``, the deterministic degraded-chip seam of
``fault/inject.py``), and the kernel-policy layer's record (ISSUE 17:
``kernel_dispatch`` — one Pallas-vs-plain path resolution by
``ops/dispatch.py`` (``model``, ``op``, resolved ``path``
``pallas``|``plain``|``ring``, the ``reason`` including the
formerly-silent below-``FLASH_MIN_SEQ_LEN`` fall-through, and ``seq_len``
where shape-dependent), deduplicated to one record per distinct decision
per process and forwarded through the sink the Trainer installs for the
run — so a "tuned" run that quietly lost its kernels is visible to the
doctor), and the serving layer's records (ISSUE 18, emitted by
``serving/server.py`` into the SAME per-run-dir flight recorder the
monitor/controller already read: ``serve_start`` — one per server
attempt (``port``, ``buckets``, admission bounds, ``slo_p99_ms``,
``params_version``, ``mesh_axes``); ``request_batch`` — the ~1 Hz
serving summary pulse doubling as the server's liveness heartbeat
(``requests``/``batches`` since the last pulse, trailing-window ``qps``,
``p50_ms``/``p99_ms``, ``slo_ok``, ``params_version``); ``hot_swap`` —
one checkpoint hot-swap under load (``checkpoint`` name,
``from_version``/``to_version``, ``swap_ms``, ``pending_requests``);
``admission_reject`` — a typed overload rejection, debounced to one
record per tenant per second (``tenant``, ``depth`` vs ``bound``,
``rejects`` since the last record; since schema 8 also ``reason``
``overload``|``draining``|``replanning`` and the ``retry_after_s`` the
refused caller was told — the backpressure signal, derived from queue
depth and the drain deadline)), and the actuated-handshake records
(ISSUE 20, emitted by ``serving/server.py``: ``offer_accept`` /
``offer_decline`` — a replica's decision on an offered chip
(``chip``, ``reason``, its ``state``/``slo_ok``/``p99_ms``/``pending``
at decision time — a replica under SLO pressure declines);
``drain_start`` — admission stops for a drain (``deadline_s``,
``pending``, ``params_version``); ``replan_done`` — the replica is
serving again on the re-planned device set (``from_mesh``/``to_mesh``
axes, ``device_ids``, requests ``shed`` past the drain deadline,
``replan_ms``, the unchanged ``params_version``, cumulative
``replans``, the elastic solver's ``plan_reason``)), and the
streaming-data layer's records
(ISSUE 19, emitted by the Trainer for any loader speaking the
reader-state surface (docs/data.md; no in-tree loader does today):
``shard_assignment`` — one per
attempt, on start and on every elastic resume (the assignment ``version``
fingerprint, ``record_count``/``shard_count``, ``global_batch_size``, this
host's ``row_lo``/``row_hi`` slice, the ``batch_extent`` it feeds, the
``resume_batch`` the cursor positions at, and ``elastic`` — whether this
attempt crossed a topology change); ``data_reader_state`` — one per
checkpoint save, the reader position a resume from that checkpoint will
consume from (``name``, resume ``epoch``, global record ``cursor``,
shuffle ``seed``, ``record_count``, ``assignment_version``)) — as one JSON
object per line,
machine-readable and append-only. Since schema 2 every record also carries ``chips`` (this
process's local device ids) and ``schema`` (:data:`SCHEMA_VERSION`), so
per-chip attribution survives elastic topology changes and consumers can
detect vocabularies they predate. Since schema 4, ``run_start`` and
``heartbeat`` records (and every ``controller_action``) also carry
``attempt`` — the monotonic per-run-dir attempt id claimed via
:func:`claim_attempt`, so one appended events.jsonl attributes each
record to the restart generation that wrote it.

Conventions:

* **Rank-0 file ownership** (the logger's multi-host convention,
  ``utils/logger.py``): only process 0 writes the file; other processes get
  a disabled no-op writer. Events are global run facts (the trainer emits
  them at points every host reaches), so one writer sees everything — and a
  shared filesystem never sees interleaved half-lines from N writers.
* **Monotonic timestamps**: every record carries ``t_mono``
  (``time.monotonic()`` — ordering-safe across NTP slews) next to ``t_wall``
  (``time.time()`` — human-correlatable). Within one process the ``t_mono``
  stream is nondecreasing by construction.
* **Append mode**: a resumed run appends to the same file, so the log shows
  the full preempt/restart history (each attempt opens with its own
  ``run_start``). Crash-safe: every record is flushed line-atomically, and
  a torn last line from a hard kill is newline-terminated on reopen so
  records never merge (``read_events(strict=False)`` audits past it).
* **Never the reason a run dies**: emit failures (disk full, permission)
  disable the log with one warning instead of raising into the step loop.
"""

from __future__ import annotations

import json
import math
import os
import socket
import threading
import time
from typing import Any, Iterator

import jax

__all__ = [
    "EventFollower",
    "EventLog",
    "SCHEMA_VERSION",
    "claim_attempt",
    "load_run_events",
    "peek_attempt",
    "read_events",
    "resolve_events_path",
]

# Record-schema version, stamped on every record as ``schema`` so offline
# consumers (the timeline exporter, the run doctor, dashboards) can detect
# a vocabulary they predate instead of misparsing it. History:
#   1 — implicit (PR 4-12 records carry no ``schema`` field);
#   2 — this field + ``chips`` identity + straggler/goodput-snapshot
#       window/epoch fields (ISSUE 13);
#   3 — the live-operations vocabulary (ISSUE 15): ``heartbeat``
#       (``source`` loop|watchdog, ``units``, ``since_progress_s``,
#       ``goodput_seconds`` snapshot — the liveness pulse) and
#       ``monitor_alert`` (``rule``, ``status``, ``value``/``threshold``
#       — a debounced monitor rule firing);
#   4 — the closed-loop vocabulary (ISSUE 16): ``attempt`` on
#       ``run_start``/``heartbeat`` (monotonic per-run-dir restart
#       generation, claimed via :func:`claim_attempt`),
#       ``controller_action`` (the fleet controller's evidenced
#       remediation decisions), and ``fault_injection``
#       ``kind="slow_chip"`` (the degraded-chip seam);
#   5 — the kernel-policy vocabulary (ISSUE 17): ``kernel_dispatch``
#       (one ops/dispatch.py Pallas-vs-plain resolution: ``model``,
#       ``op``, ``path``, ``reason``, optional ``seq_len`` — deduplicated
#       per distinct decision per process);
#   6 — the serving vocabulary (ISSUE 18): ``serve_start``,
#       ``request_batch`` (the server's liveness pulse), ``hot_swap``,
#       ``admission_reject`` (serving/server.py), and ``offer_chip``
#       joins the ``controller_action`` action vocabulary (a mixed-fleet
#       controller offering a freed chip to a serving replica);
#   7 — the streaming-data vocabulary (ISSUE 19): ``shard_assignment``
#       (one per attempt: the per-host split of the deterministic global
#       record sequence — version fingerprint, row range, batch extent,
#       resume batch) and ``data_reader_state`` (one per checkpoint save:
#       the epoch/cursor/seed a resume will consume from);
#   8 — the actuated-handshake vocabulary (ISSUE 20): ``offer_accept`` /
#       ``offer_decline`` (a serving replica's decision on an offered
#       chip), ``drain_start`` / ``replan_done`` (the graceful-drain +
#       live-re-plan cycle), ``reason``/``retry_after_s`` on
#       ``admission_reject``, and ``state``/``qps_per_chip``/
#       ``mesh_chips``/``shed_total`` on the ``request_batch`` pulse.
SCHEMA_VERSION = 8


def _jsonable(value: Any) -> Any:
    """Best-effort scalar coercion: numpy/jax scalars -> python, everything
    non-serializable -> repr (an event must never fail to serialize).

    Non-finite floats become their repr strings ("nan"/"inf"/"-inf"):
    json.dumps would otherwise emit bare ``NaN``/``Infinity`` literals —
    Python-parseable but invalid strict JSON, which jq / JSON.parse reject.
    The value (e.g. an anomaly's NaN loss) is payload, so it is preserved
    as a string rather than dropped."""
    if isinstance(value, float):
        return value if math.isfinite(value) else repr(value)
    if value is None or isinstance(value, (bool, int, str)):
        return value
    if isinstance(value, dict):
        return {str(k): _jsonable(v) for k, v in value.items()}
    if isinstance(value, (list, tuple)):
        return [_jsonable(v) for v in value]
    try:
        value = float(value)  # numpy / jax 0-d scalars
    except (TypeError, ValueError):
        return repr(value)
    return value if math.isfinite(value) else repr(value)


class EventLog:
    """``EventLog(path).emit("checkpoint_save", name="last", epoch=3)``.

    ``path=None`` (or a non-zero process index) constructs a disabled no-op
    writer — the universal telemetry-off contract, mirroring
    ``utils.tensorboard.MetricsWriter``.
    """

    def __init__(self, path: str | None, *, process_index: int | None = None):
        self._path = path
        self._file = None
        self._dead = False  # a failed write disables the log permanently
        proc = jax.process_index() if process_index is None else process_index
        self.process = proc
        self.enabled = path is not None and proc == 0
        self._host = socket.gethostname()
        # Chip identity (ISSUE 13): the local device ids this process owns,
        # as one compact string stamped on every record — so per-chip
        # attribution (straggler skew, memory skew) stays coherent across
        # an elastic N->M resume, where the SAME appended log suddenly
        # describes a different topology. Resolved lazily at the first
        # enabled emit: a disabled log (telemetry off / non-zero rank) must
        # not force jax backend initialization beyond what the
        # process_index read above already did.
        self._chips: str | None = None
        # Emits may come from the async-checkpoint commit worker as well as
        # the main thread; timestamping AND writing under one lock keeps the
        # file's t_mono stream nondecreasing (two threads reading the clock
        # then writing in the other order would interleave otherwise).
        self._emit_lock = threading.Lock()

    def _open(self):
        if self._file is None:
            os.makedirs(os.path.dirname(os.path.abspath(self._path)), exist_ok=True)
            # Torn-last-line repair: a hard kill (SIGKILL, power loss) can
            # leave a partial record with no trailing newline; appending the
            # resumed run's first event onto it would merge two records into
            # one unparseable line. Terminate the fragment first — it stays
            # in the log as its own (malformed) line marking the crash.
            try:
                with open(self._path, "rb") as f:
                    f.seek(-1, os.SEEK_END)
                    torn = f.read(1) != b"\n"
            except (OSError, ValueError):  # missing or empty file
                torn = False
            self._file = open(self._path, "a", encoding="utf-8")
            if torn:
                self._file.write("\n")
        return self._file

    def emit(self, event: str, **fields) -> dict | None:
        """Append one event record; returns the record dict (or None when
        disabled). Field values are coerced to JSON-safe scalars."""
        if not self.enabled or self._dead:
            return None
        if self._chips is None:
            try:
                self._chips = ",".join(str(d.id) for d in jax.local_devices())
            except RuntimeError:
                self._chips = ""  # backend unavailable: identity degrades, log lives
        with self._emit_lock:
            record = {
                "event": str(event),
                "t_wall": time.time(),
                "t_mono": time.monotonic(),
                "process": self.process,
                "host": self._host,
                "pid": os.getpid(),
                "chips": self._chips,
                "schema": SCHEMA_VERSION,
            }
            for key, value in fields.items():
                record[str(key)] = _jsonable(value)
            try:
                f = self._open()
                f.write(json.dumps(record) + "\n")
                f.flush()
            except OSError as e:
                # Telemetry must never kill training: disable and move on.
                self._dead = True
                import warnings

                warnings.warn(f"EventLog disabled — write to {self._path!r} failed: {e}")
                return None
        return record

    def close(self) -> None:
        if self._file is not None:
            try:
                self._file.close()
            except OSError:
                pass
            self._file = None  # a later emit() lazily reopens (append mode)

    def __enter__(self) -> "EventLog":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def _parse_tolerant(raw: bytes | str, lineno: int, path: str) -> dict | None:
    """Parse ONE event-log line the tolerant way (the post-crash-audit
    contract of ``read_events(strict=False)``): blank lines skip silently,
    malformed JSON (a torn fragment from a hard kill, a corrupted write)
    skips with a warning naming the file line, and only dict records
    survive (a bare JSON scalar cannot carry an ``event`` field and would
    crash every consumer downstream)."""
    if isinstance(raw, bytes):
        try:
            raw = raw.decode("utf-8")
        except UnicodeDecodeError as e:
            import warnings

            warnings.warn(f"{path}:{lineno}: skipping undecodable event line: {e}")
            return None
    line = raw.strip()
    if not line:
        return None
    try:
        record = json.loads(line)
    except json.JSONDecodeError as e:
        import warnings

        warnings.warn(f"{path}:{lineno}: skipping malformed event line: {e}")
        return None
    if not isinstance(record, dict):
        import warnings

        warnings.warn(
            f"{path}:{lineno}: skipping non-object event line ({type(record).__name__})"
        )
        return None
    return record


def resolve_events_path(run_dir: str) -> str:
    """Map a run directory (the Trainer ``save_folder``) to its event-log
    path — or pass a direct ``.jsonl``/existing-file path through. The
    ONE layout rule (``<save_folder>/telemetry/events.jsonl``) shared by
    the timeline exporter, the run doctor, and the live monitor.

    Resolution is by suffix/file-ness rather than ``isdir``: a monitor is
    deliberately allowed to attach BEFORE the run creates its directory
    (the EventFollower yields ``[]`` until the first emit), and an
    isdir-based rule would freeze a not-yet-existing run dir into a
    direct-file path that never resolves."""
    if run_dir.endswith(".jsonl") or os.path.isfile(run_dir):
        return run_dir
    return os.path.join(run_dir, "telemetry", "events.jsonl")


def _attempt_path(run_dir: str) -> str:
    """Sidecar path of the attempt counter: next to events.jsonl, NOT inside
    it — the counter must survive (and be readable before) any event emit,
    and a controller process must read it without tailing the log."""
    return os.path.join(run_dir, "telemetry", "attempt")


def peek_attempt(run_dir: str) -> int:
    """The last attempt id claimed for ``run_dir`` (0 when none yet).
    Stdlib-only and side-effect-free — safe from a supervising controller."""
    try:
        with open(_attempt_path(run_dir), encoding="utf-8") as f:
            return int(f.read().strip() or 0)
    except (OSError, ValueError):
        return 0


def claim_attempt(run_dir: str) -> int:
    """Claim the next monotonic attempt id for ``run_dir`` (1, 2, 3, ...).

    Called once per trainer process at run start (rank 0, telemetry on);
    the id is stamped on that attempt's ``run_start``/``heartbeat`` records
    and into checkpoint meta, so one appended events.jsonl — and the
    checkpoints it describes — attribute every record to the restart
    generation that wrote it (ISSUE 16). The write is tmp + ``os.replace``
    so a crash mid-claim never leaves a torn counter; restarts are
    serialized by the supervisor (a run dir has at most one live trainer),
    so no cross-process lock is needed."""
    sidecar = _attempt_path(run_dir)
    os.makedirs(os.path.dirname(sidecar), exist_ok=True)
    attempt = peek_attempt(run_dir) + 1
    tmp = sidecar + ".tmp"
    with open(tmp, "w", encoding="utf-8") as f:  # jaxlint: disable=file-write-without-rank-gate -- call site is process_index()==0-gated in train(); the gate lives with the Telemetry rank check, not in this stdlib helper
        f.write(f"{attempt}\n")
    os.replace(tmp, sidecar)
    return attempt


class EventFollower:
    """Incremental, torn-line-tolerant reader over one events.jsonl file —
    THE shared parser behind :func:`load_run_events` (the one-shot
    consumers: timeline exporter, run doctor) and the live monitor's tail
    (``telemetry/monitor.py``), so the two cannot drift (ISSUE 15).

    Each :meth:`poll` returns the records whose lines became COMPLETE
    (newline-terminated) since the last poll, each stamped with ``_line``
    (the 1-based FILE line — blank and malformed lines still advance it,
    so citations stay stable past the lines the tolerant parse skipped).
    A trailing fragment with no newline is *withheld*, not rejected: a
    live writer may still be mid-``write`` on it, and the next poll picks
    it up once the newline lands. ``poll(final=True)`` — for post-mortem
    reads, where no more bytes are coming — additionally parses the
    unterminated tail (a complete record whose writer died before the
    newline is data; a torn fragment warns and skips, exactly like
    ``read_events(strict=False)``).

    A file that does not exist yet yields ``[]`` (the monitor may attach
    before the run's first emit); a file that SHRANK (a fresh attempt
    truncating, a rotation) resets the cursor and re-reads from the top —
    stale offsets must never silently hide a restarted run's records.
    """

    def __init__(self, path: str):
        self.path = path
        self._offset = 0  # bytes consumed through the last complete line
        self._lineno = 0  # 1-based count of completed lines seen
        self._partial = b""  # unterminated tail carried between polls
        self._tail_emitted: bytes | None = None  # tail a final poll yielded
        # Bumped on every truncation reset, so a stateful consumer (the
        # monitor's Signals fold) knows its accumulated state describes a
        # file that no longer exists and must be rebuilt.
        self.generation = 0

    def poll(self, *, final: bool = False) -> list[dict]:
        try:
            size = os.path.getsize(self.path)
        except OSError:
            return []  # not written yet (or vanished): nothing to report
        if size < self._offset:
            # Truncated/rotated underneath us: start over from the top.
            self._offset = 0
            self._lineno = 0
            self._partial = b""
            self._tail_emitted = None
            self.generation += 1
        try:
            with open(self.path, "rb") as f:
                f.seek(self._offset)
                data = f.read()
        except OSError:
            return []
        self._offset += len(data)
        chunks = (self._partial + data).split(b"\n")
        self._partial = chunks.pop()  # b"" when the data ended on a newline
        records = []
        for raw in chunks:
            self._lineno += 1
            if self._tail_emitted is not None:
                # A prior final poll already yielded this exact tail; its
                # newline landing now must not re-yield it (a monitor that
                # declared a stalled writer dead, then saw it resurrect).
                already, self._tail_emitted = raw == self._tail_emitted, None
                if already:
                    continue
            rec = _parse_tolerant(raw, self._lineno, self.path)
            if rec is not None:
                rec["_line"] = self._lineno
                records.append(rec)
        if final and self._partial.strip() and self._partial != self._tail_emitted:
            # Parse the unterminated tail WITHOUT consuming it: offset,
            # line counter, and buffer stay put, so a writer that was only
            # stalled (not dead) and later completes the line is read
            # normally — no lost record, no drifted _line citations. A
            # complete record missing only its newline is remembered in
            # _tail_emitted so the newline's eventual arrival dedupes.
            rec = _parse_tolerant(self._partial, self._lineno + 1, self.path)
            if rec is not None:
                rec["_line"] = self._lineno + 1
                records.append(rec)
                self._tail_emitted = self._partial
        return records


def load_run_events(run_dir: str) -> list[dict]:
    """Read a run directory's (or a direct ``.jsonl`` path's) event log,
    tolerant of a torn last line (post-crash audits are a primary
    consumer). Each record gains a ``_line`` field — the 1-based position
    in the file — so doctor evidence and timeline args can cite it.

    One shot through the SAME :class:`EventFollower` the live monitor
    tails with (``final=True``: the unterminated tail of a killed writer
    is parsed rather than withheld) — the batch load IS the follower run
    to completion, so the two read paths cannot drift."""
    path = resolve_events_path(run_dir)
    if not os.path.isfile(path):
        raise FileNotFoundError(
            f"no event log at {path} — was the run telemetry-off? "
            "(Trainer(telemetry='on') writes <save_folder>/telemetry/events.jsonl)"
        )
    return EventFollower(path).poll(final=True)


def read_events(
    path: str, *, strict: bool = True, with_lineno: bool = False
) -> Iterator[dict]:
    """Parse an event log back into dicts — the test/smoke-side consumer.

    ``strict=True`` (default) raises ``ValueError`` naming the offending
    line on malformed JSONL — the CI-gate behavior, where a bad line means
    the writer regressed. ``strict=False`` skips malformed lines with a
    warning — for post-crash audits, where a torn fragment from a hard kill
    (see ``EventLog._open``'s repair) is expected and the surviving record
    stream is the point. ``with_lineno=True`` yields ``(lineno, record)``
    pairs instead — the 1-based FILE line, which a consumer citing lines
    (the run doctor's evidence rows) needs: a yielded-record index drifts
    past every blank/torn line the tolerant mode just skipped."""
    with open(path, encoding="utf-8") as f:
        for lineno, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            if not strict:
                # The ONE tolerant parse (shared with EventFollower).
                record = _parse_tolerant(line, lineno, path)
                if record is not None:
                    yield (lineno, record) if with_lineno else record
                continue
            try:
                record = json.loads(line)
                yield (lineno, record) if with_lineno else record
            except json.JSONDecodeError as e:
                raise ValueError(
                    f"{path}:{lineno}: malformed event line: {e}"
                ) from e
