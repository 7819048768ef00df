"""Supervised process runner for embarrassingly-parallel grids.

``repro.core.collect`` used to fan its (workload × job) cells out to a raw
``multiprocessing.Pool`` — one hung route search stalled the sweep forever
and one dead worker (OOM kill, segfault, ``kill -9``) aborted it with a
cryptic pool error.  :class:`SupervisedRunner` replaces it with a
supervisor that treats worker death and wall-clock overruns as *data*:

* **one process per cell attempt** — a crash or kill is perfectly
  isolated (nothing else shares the dying process), and "respawn" is
  inherent: the next attempt or cell gets a fresh worker;
* **hard per-cell timeouts** — a cell past ``timeout_s`` is terminated
  (SIGTERM, then SIGKILL) and reported as a
  :class:`~repro.compiler.errors.CompileTimeout` failure, reclaiming the
  slot for the rest of the grid;
* **dead-worker detection** — a worker that exits without delivering a
  result (EOF on its result pipe) is a
  :class:`~repro.compiler.errors.WorkerCrashed` failure carrying the
  observed exit status;
* **bounded deterministic retry** — crashes and *transient* errors
  (:data:`~repro.compiler.errors.RETRYABLE_ERRORS`, matched against the
  raised type's MRO) are retried up to ``retries`` extra attempts with
  exponential backoff (``backoff_s * 2**(attempt-1)``); deterministic
  failures (a mapper ``ValueError``, a timeout of a deterministic
  compile) fail fast;
* **structured failure records** — the caller receives a
  :class:`CellFailure` per exhausted cell instead of an exception, so a
  grid sweep always completes and records *what* failed where.

Workers learn their attempt index through the
``REPRO_RUNNER_ATTEMPT`` environment variable (see
:mod:`repro.compiler.faultinject` — attempt-scoped fault specs model
transient faults that heal on retry).

Workers never touch the accelerator: each pins the batched simulator to
the host ``numpy`` backend (``REPRO_SIM_BACKEND``) before its task runs.
A chip belongs to one process at a time, so device verification happens
only in single-process paths (``plaid-compile verify``, ``collect
--batch-verify`` after the sweep, ``chip_smoke.py``).

The task function and the task payloads must be picklable top-level
objects under the ``spawn`` start method; under ``fork`` (the Linux
default) anything goes.  Results stream back in completion order, like
``Pool.imap_unordered``.
"""
from __future__ import annotations

import os
import time
from collections import deque
from dataclasses import dataclass, field
from multiprocessing import connection, get_context
from typing import Callable, Dict, Iterable, Iterator, List, Optional, Tuple

from repro.compiler.errors import (
    RETRYABLE_ERRORS,
    CompileTimeout,
    WorkerCrashed,
    classify,
)
from repro.compiler.faultinject import ATTEMPT_VAR
from repro.sim.batch import ENV_BACKEND

#: grace between SIGTERM and SIGKILL when reclaiming a timed-out worker
_TERM_GRACE_S = 1.0


@dataclass
class CellFailure:
    """Structured record of one cell that exhausted its attempts."""

    label: str                      # caller-supplied cell label
    error: str                      # taxonomy class name (classify())
    message: str
    attempts: int                   # attempts actually made
    wall_s: float                   # wall time across all attempts
    exitcode: Optional[int] = None  # crash exit status (negative = signal)
    traceback: Optional[str] = None  # worker-side traceback, when reported

    def to_json(self) -> Dict[str, object]:
        out: Dict[str, object] = {
            "error": self.error,
            "message": self.message,
            "attempts": self.attempts,
            "wall_s": round(self.wall_s, 3),
        }
        if self.exitcode is not None:
            out["exitcode"] = self.exitcode
        if self.traceback:
            out["traceback"] = self.traceback
        return out


def _child_main(fn: Callable, task, attempt: int, conn_w) -> None:
    """Worker entry: run one task, report ("ok", result) or ("err", mro
    names, message, traceback) over the pipe, exit.  Top-level so the
    ``spawn`` start method can import it."""
    os.environ[ATTEMPT_VAR] = str(attempt)
    os.environ[ENV_BACKEND] = "numpy"
    try:
        result = fn(task)
        payload = ("ok", result)
    except BaseException as e:  # noqa: BLE001 - the supervisor classifies
        import traceback as _tb

        payload = ("err", [c.__name__ for c in type(e).__mro__],
                   classify(e), str(e), _tb.format_exc())
    try:
        conn_w.send(payload)
    except (BrokenPipeError, OSError):
        pass  # supervisor already gave up on us (timeout); nothing to do
    finally:
        conn_w.close()


@dataclass
class _Pending:
    idx: int
    task: object
    attempt: int = 0          # next attempt index (0 = first try)
    not_before: float = 0.0   # monotonic backoff gate
    spent_s: float = 0.0      # wall time burned by previous attempts


@dataclass
class _InFlight:
    pend: _Pending
    proc: object
    conn_r: object
    t_start: float
    deadline: Optional[float]


@dataclass
class SupervisedRunner:
    """See module docstring.

    ``fn``           — picklable task function, called as ``fn(task)``;
    ``jobs``         — concurrent worker slots;
    ``timeout_s``    — hard per-cell wall-clock limit (``None`` = none);
    ``retries``      — extra attempts for crashes/transient errors;
    ``backoff_s``    — base retry backoff (exponential, deterministic);
    ``retry_timeouts`` — also retry timed-out cells (off by default: a
    deterministic compile that hung once will hang again);
    ``start_method`` — multiprocessing start method (``None`` = platform
    default, i.e. ``fork`` on Linux);
    ``label``        — maps a task to the cell label used in failure
    records and fault matching.
    """

    fn: Callable
    jobs: int = 1
    timeout_s: Optional[float] = None
    retries: int = 1
    backoff_s: float = 0.1
    retry_timeouts: bool = False
    start_method: Optional[str] = None
    label: Callable[[object], str] = field(default=repr)

    def run(self, tasks: Iterable) -> Iterator[Tuple[object, str, object]]:
        """Yield ``(task, "ok", result)`` / ``(task, "failed",
        CellFailure)`` in completion order; every input task yields
        exactly once."""
        ctx = get_context(self.start_method)
        pending = deque(_Pending(i, t) for i, t in enumerate(tasks))
        inflight: Dict[object, _InFlight] = {}  # conn_r -> record
        try:
            while pending or inflight:
                now = time.monotonic()
                # dispatch into free slots (skip cells still in backoff)
                n_ready = sum(1 for p in pending if p.not_before <= now)
                while len(inflight) < max(1, self.jobs) and n_ready > 0:
                    pend = pending.popleft()
                    if pend.not_before > now:
                        pending.append(pend)  # rotate past backoff gates
                        continue
                    n_ready -= 1
                    rec = self._spawn(ctx, pend)
                    inflight[rec.conn_r] = rec
                if not inflight:
                    # everything runnable is in backoff: sleep to the gate
                    gate = min(p.not_before for p in pending)
                    time.sleep(max(0.0, gate - time.monotonic()))
                    continue
                yield from self._reap(pending, inflight)
        finally:
            for rec in inflight.values():  # GeneratorExit/KeyboardInterrupt
                self._reclaim(rec.proc)

    # -- internals -----------------------------------------------------------
    def _spawn(self, ctx, pend: _Pending) -> _InFlight:
        conn_r, conn_w = ctx.Pipe(duplex=False)
        proc = ctx.Process(
            target=_child_main,
            args=(self.fn, pend.task, pend.attempt, conn_w),
            daemon=True,
        )
        proc.start()
        # the parent MUST drop its copy of the write end: EOF (= worker
        # died without reporting) is only observable once the child holds
        # the last open handle
        conn_w.close()
        now = time.monotonic()
        deadline = None if self.timeout_s is None else now + self.timeout_s
        return _InFlight(pend, proc, conn_r, now, deadline)

    def _wait_timeout(self, pending, inflight) -> float:
        now = time.monotonic()
        horizon = now + 0.5
        for rec in inflight.values():
            if rec.deadline is not None:
                horizon = min(horizon, rec.deadline)
        for p in pending:
            if p.not_before > now:
                horizon = min(horizon, p.not_before)
        return max(0.0, horizon - now)

    def _reap(self, pending, inflight) -> Iterator[Tuple[object, str, object]]:
        ready = connection.wait(list(inflight),
                                timeout=self._wait_timeout(pending, inflight))
        for conn_r in ready:
            rec = inflight.pop(conn_r)
            try:
                msg = conn_r.recv()
            except (EOFError, OSError):
                msg = None  # died without a result: crash
            conn_r.close()
            self._reclaim(rec.proc)
            yield from self._settle(pending, rec, msg)
        now = time.monotonic()
        for conn_r, rec in list(inflight.items()):
            if rec.deadline is not None and now >= rec.deadline:
                del inflight[conn_r]
                self._reclaim(rec.proc, force=True)
                conn_r.close()
                yield from self._settle(pending, rec, ("timeout",))

    def _settle(self, pending, rec: _InFlight,
                msg) -> Iterator[Tuple[object, str, object]]:
        pend = rec.pend
        wall = pend.spent_s + (time.monotonic() - rec.t_start)
        attempt = pend.attempt
        made = attempt + 1
        if msg is not None and msg[0] == "ok":
            yield pend.task, "ok", msg[1]
            return
        if msg is None:  # crashed
            exitcode = rec.proc.exitcode
            retryable = True
            fail = CellFailure(
                label=self.label(pend.task),
                error=classify(WorkerCrashed("")),
                message=(f"worker exited with status {exitcode} before "
                         f"reporting a result"),
                attempts=made, wall_s=wall, exitcode=exitcode,
            )
        elif msg[0] == "timeout":
            retryable = self.retry_timeouts
            fail = CellFailure(
                label=self.label(pend.task),
                error=classify(CompileTimeout("")),
                message=(f"cell exceeded the per-cell timeout of "
                         f"{self.timeout_s}s"),
                attempts=made, wall_s=wall,
            )
        else:  # ("err", mro_names, taxonomy_label, message, traceback)
            _, mro, label, text, tb = msg
            retryable = any(name in RETRYABLE_ERRORS for name in mro)
            fail = CellFailure(
                label=self.label(pend.task), error=label, message=text,
                attempts=made, wall_s=wall, traceback=tb,
            )
        if retryable and attempt < self.retries:
            pend.attempt += 1
            pend.spent_s = wall
            pend.not_before = (time.monotonic()
                               + self.backoff_s * (2 ** attempt))
            pending.append(pend)
            return
        yield pend.task, "failed", fail

    @staticmethod
    def _reclaim(proc, force: bool = False):
        """Join a finished worker; terminate (then kill) one we gave up
        on so no zombie or stray compute outlives its cell."""
        if force and proc.is_alive():
            proc.terminate()
            proc.join(_TERM_GRACE_S)
            if proc.is_alive():
                proc.kill()
        proc.join()


def run_supervised(fn: Callable, tasks: Iterable, **cfg
                   ) -> Iterator[Tuple[object, str, object]]:
    """Convenience wrapper: ``SupervisedRunner(fn, **cfg).run(tasks)``."""
    return SupervisedRunner(fn, **cfg).run(tasks)
