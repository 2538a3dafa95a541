"""Execution transports: how ASSIGN/DONE messages physically move.

One worker loop serves both live backends — it only needs a blocking
``get(timeout)`` inbox and a ``to_manager(msg)`` callable:

  * :class:`ThreadTransport` — in-process ``queue.Queue`` mailboxes
    (migrated from the old core/selfsched.py runtime).
  * :class:`ProcessTransport` — one job's view of a :class:`WorkerPool`:
    ``multiprocessing`` queues + one OS process per worker, the real
    process isolation of triples-mode NPPN, launched once and kept
    across jobs.  Results ride back inside DONE messages (no shared
    memory), exactly like the paper's manager/worker messaging.  A
    worker function with device calls gets a :class:`DeviceOwner` in the
    manager's process, which alone holds the chip: the worker processes
    do the host work and send each device call to the owner.

``fail_after`` kills a worker after N completed tasks (fault-injection
hook for tests): the worker returns without sending DONE, exactly like a
node death mid-batch.

Each DONE carries, per task, the start and length of its execution and
the worker thread's CPU seconds (``time.thread_time``): the manager's
``exec`` spans.  Given a :class:`repro.obs.Tracer`, a worker binds its
thread to its id and the task ids it runs, so the stage spans of the
worker function carry both.  A worker thread binds the manager's
tracer; a worker process binds a ring of its own for each traced
message and returns what it recorded in the DONE, which the transport
adds to the manager's ring (``time.monotonic`` is one clock for every
process of a Linux host).
"""

from __future__ import annotations

import abc
import dataclasses
import multiprocessing
import os
import queue
import sys
import threading
import time
import traceback
from typing import Any, Callable, Optional, Sequence

from repro.core.messages import Message, MessageKind, Task
from repro.runtime.protocol import DEFAULT_POLL_INTERVAL_S

__all__ = ["Transport", "ThreadTransport", "ProcessTransport", "WorkerPool",
           "DeviceOwner", "DeviceClient", "OWNER_TRACK", "NO_BACKEND",
           "worker_loop"]

BatchFn = Callable[[list[Task]], dict]


def worker_loop(worker_id: str, inbox, to_manager: Callable[[Message], None],
                fn: Callable[[Task], Any], *,
                batch_fn: Optional[BatchFn] = None,
                poll_interval: float = DEFAULT_POLL_INTERVAL_S,
                heartbeat_interval: Optional[float] = None,
                fail_after: Optional[int] = None,
                slow_factor: Optional[float] = None,
                tracer: Optional[Any] = None,
                ring: Optional["_ProcessRing"] = None) -> None:
    """A worker process: poll for ASSIGN, run, report DONE, repeat.

    "While idle, the workers wait 0.3 seconds prior between checking if
    another task was sent from the manager."  When ``batch_fn`` is given,
    a multi-task ASSIGN executes as ONE call (e.g. a single vectorized
    pallas invocation over every task in the message) instead of per-task
    Python dispatch; ``batch_fn`` returns a dict of task_id -> result.

    ``slow_factor`` > 1 makes this worker run that many times slower (it
    sleeps ``(slow_factor - 1) x elapsed`` after each execution) — the
    live mirror of the sim's ``worker_speed`` straggler injection, used
    to exercise speculation and speed-fed sizing on real threads.

    Heartbeats run on a side thread so a worker keeps beating *through*
    long task executions — manager-side silence therefore means the
    worker is gone (crash/kill), never merely busy.  A task stuck forever
    still heartbeats; guarding against that needs task-level timeouts.

    ``tracer`` (a worker thread) is the manager's ring; ``ring`` (a
    worker process) is the process's own, bound for the messages whose
    ASSIGN asks for spans (``Message.trace``) and emptied into their
    DONE or FAILED (``Message.events``).  Replies echo the ASSIGN's
    ``job``.
    """
    # Announce liveness immediately: spawn-based workers can take seconds
    # to boot, and the manager must not confuse booting with death.
    to_manager(Message(MessageKind.HEARTBEAT, sender=worker_id))
    stop_heartbeats = None
    if heartbeat_interval is not None:
        stop_heartbeats = threading.Event()

        def _beat() -> None:
            while not stop_heartbeats.wait(heartbeat_interval):
                to_manager(Message(MessageKind.HEARTBEAT, sender=worker_id))

        threading.Thread(target=_beat, name=f"heartbeat-{worker_id}",
                         daemon=True).start()
    try:
        _worker_recv_loop(worker_id, inbox, to_manager, fn, batch_fn,
                          poll_interval, fail_after, slow_factor, tracer,
                          ring)
    finally:
        if stop_heartbeats is not None:
            stop_heartbeats.set()


def _worker_recv_loop(worker_id, inbox, to_manager, fn, batch_fn,
                      poll_interval, fail_after,
                      slow_factor=None, tracer=None, ring=None) -> None:
    completed = 0
    drag = (slow_factor - 1.0) if slow_factor and slow_factor > 1.0 else 0.0
    while True:
        try:
            msg = inbox.get(timeout=poll_interval)
        except queue.Empty:
            continue
        if msg.kind is MessageKind.SHUTDOWN:
            return
        assert msg.kind is MessageKind.ASSIGN
        if ring is not None:
            tracer = ring.begin(msg.trace)

        def reply(kind, **fields) -> Message:
            events = ring.take() if ring is not None else ()
            return Message(kind, sender=worker_id, events=events,
                           job=msg.job, **fields)

        tasks = list(msg.tasks)
        done_ids: list[str] = []
        res: list[Any] = []
        spans: list[tuple[float, float, float]] = []
        t0 = time.monotonic()
        if batch_fn is not None and len(tasks) > 1:
            if fail_after is not None and completed + len(tasks) > fail_after:
                return  # simulate node death mid-batch: no DONE sent
            ids = tuple(t.task_id for t in tasks)
            if tracer is not None:
                tracer.bind(worker_id, ids)
            c0 = time.thread_time()
            try:
                out = batch_fn(tasks)
            except Exception as e:  # whole batch fails together
                to_manager(reply(MessageKind.FAILED, task_ids=ids,
                                 error=repr(e)))
                continue
            finally:
                if tracer is not None:
                    tracer.bind(None)
            if drag:
                time.sleep(drag * (time.monotonic() - t0))
            # One call for every task: its window and CPU, split evenly.
            step = (time.monotonic() - t0) / len(tasks)
            cpu = (time.thread_time() - c0) / len(tasks)
            for i, t in enumerate(tasks):
                done_ids.append(t.task_id)
                res.append(out.get(t.task_id) if isinstance(out, dict)
                           else out)
                spans.append((t0 + i * step, step, cpu))
            completed += len(tasks)
        else:
            for task in tasks:
                if fail_after is not None and completed >= fail_after:
                    return  # simulate node death mid-batch: no DONE sent
                if tracer is not None:
                    tracer.bind(worker_id, (task.task_id,))
                t_task = time.monotonic()
                c_task = time.thread_time()
                try:
                    r = fn(task)
                except Exception as e:  # report, don't die
                    to_manager(reply(MessageKind.FAILED,
                                     task_ids=(task.task_id,),
                                     error=repr(e)))
                    continue
                finally:
                    if tracer is not None:
                        tracer.bind(None)
                if drag:
                    time.sleep(drag * (time.monotonic() - t_task))
                spans.append((t_task, time.monotonic() - t_task,
                              time.thread_time() - c_task))
                done_ids.append(task.task_id)
                res.append(r)
                completed += 1
        if done_ids:
            # Worker fns may expose take_wait_s() (return-and-reset feed
            # wait accumulated in THIS thread/process, e.g. store decode
            # stalls); it rides back in the DONE so the manager can split
            # busy time into compute vs I/O wait.
            take_wait = getattr(fn, "take_wait_s", None)
            wait_s = float(take_wait()) if take_wait is not None else 0.0
            to_manager(reply(
                MessageKind.DONE,
                task_ids=tuple(done_ids), results=tuple(res),
                busy_seconds=time.monotonic() - t0,
                wait_seconds=wait_s, task_spans=tuple(spans)))


class Transport(abc.ABC):
    """Message delivery + worker lifecycle for one live backend."""

    worker_ids: list[str]

    @abc.abstractmethod
    def start(self) -> None:
        """Launch the workers."""

    @abc.abstractmethod
    def send(self, worker_id: str, msg: Message) -> None:
        """Deliver a message to one worker's inbox."""

    @abc.abstractmethod
    def recv_nowait(self) -> Optional[Message]:
        """Pop one message from the manager inbox, or None."""

    @abc.abstractmethod
    def stop(self) -> None:
        """Shut every worker down (idempotent)."""

    def worker_alive(self, worker_id: str) -> bool:
        """Best-effort liveness probe (used to avoid declaring a
        still-booting worker dead before its first message)."""
        return True


class _WorkerSettings:
    """Worker ids and the settings each worker loop takes: shared by the
    worker threads' transport and the worker processes' pool."""

    def __init__(self, n_workers: int, fn: Callable[[Task], Any], *,
                 batch_fn: Optional[BatchFn] = None,
                 poll_interval: float = DEFAULT_POLL_INTERVAL_S,
                 heartbeat_interval: Optional[float] = None,
                 worker_fail_after: Optional[dict[str, int]] = None,
                 worker_slow_factor: Optional[dict[str, float]] = None):
        if n_workers < 1:
            raise ValueError("need at least one worker")
        self.worker_ids = [f"w{i}" for i in range(n_workers)]
        self._fn = fn
        self._batch_fn = batch_fn
        self._poll_interval = poll_interval
        self._heartbeat_interval = heartbeat_interval
        self._fail_after = worker_fail_after or {}
        self._slow_factor = worker_slow_factor or {}

    def _worker_kwargs(self, wid: str) -> dict:
        return dict(batch_fn=self._batch_fn,
                    poll_interval=self._poll_interval,
                    heartbeat_interval=self._heartbeat_interval,
                    fail_after=self._fail_after.get(wid),
                    slow_factor=self._slow_factor.get(wid))


class ThreadTransport(_WorkerSettings, Transport):
    """In-memory mailboxes: one inbox per worker thread + manager inbox.

    The only elastic live transport: :meth:`add_worker` spawns a fresh
    worker thread mid-run and :meth:`retire_worker` shuts one down, which
    is what the :class:`~repro.runtime.fleet.FleetController` drives
    through the live ``drive()`` loop.  ``tracer`` binds each worker
    thread's stage spans to its id and tasks.
    """

    def __init__(self, *args, tracer: Optional[Any] = None, **kwargs):
        super().__init__(*args, **kwargs)
        self._tracer = tracer
        self._stopped = False
        self._inboxes: dict[str, "queue.Queue[Message]"] = {
            wid: queue.Queue() for wid in self.worker_ids}
        self._mgr_inbox: "queue.Queue[Message]" = queue.Queue()
        self._threads: list[threading.Thread] = []
        self._by_id: dict[str, threading.Thread] = {}
        self._next_id = len(self.worker_ids)

    def _spawn(self, wid: str) -> None:
        th = threading.Thread(
            target=worker_loop, name=f"worker-{wid}", daemon=True,
            args=(wid, self._inboxes[wid], self._mgr_inbox.put,
                  self._fn),
            kwargs=dict(self._worker_kwargs(wid), tracer=self._tracer))
        th.start()
        self._threads.append(th)
        self._by_id[wid] = th

    def start(self) -> None:
        for wid in self.worker_ids:
            self._spawn(wid)

    def add_worker(self) -> str:
        """Spawn one new worker thread mid-run; returns its id."""
        wid = f"w{self._next_id}"
        self._next_id += 1
        self._inboxes[wid] = queue.Queue()
        self.worker_ids.append(wid)
        self._spawn(wid)
        return wid

    def retire_worker(self, worker_id: str) -> None:
        """Shut one worker down (graceful: it drains its inbox up to the
        SHUTDOWN message; the caller only retires idle workers)."""
        self._inboxes[worker_id].put(Message(MessageKind.SHUTDOWN, "manager"))

    def send(self, worker_id: str, msg: Message) -> None:
        self._inboxes[worker_id].put(msg)

    def recv_nowait(self) -> Optional[Message]:
        try:
            return self._mgr_inbox.get_nowait()
        except queue.Empty:
            return None

    def stop(self) -> None:
        if self._stopped:
            return
        self._stopped = True
        for wid in self.worker_ids:
            self._inboxes[wid].put(Message(MessageKind.SHUTDOWN, "manager"))
        for th in self._threads:
            th.join(timeout=5.0)

    def worker_alive(self, worker_id: str) -> bool:
        th = self._by_id.get(worker_id)
        return th is not None and th.is_alive()


# ---------------------------------------------------------------------------
# worker processes
# ---------------------------------------------------------------------------

#: The timeline row of the device owner's spans.
OWNER_TRACK = "device-owner"

#: What a worker process that must not initialise a JAX backend sets as
#: ``jax_platforms``: it names no platform, so the first call that needs
#: a backend raises instead of taking the chip or the CPU interpreter.
NO_BACKEND = "none_in_worker_processes"


def _nbytes(obj) -> int:
    """Bytes of the arrays in a tuple, or among a dict's values."""
    items = obj.values() if isinstance(obj, dict) else obj
    return sum(int(getattr(x, "nbytes", 0)) for x in items)


class DeviceOwner:
    """Runs the device calls of a pool's worker processes in the
    manager's process, the one process that holds the chip.

    Each worker process has one connection to the owner.  A link thread
    per connection receives a request ``(task_id, args, counters)``,
    queues it and sends the reply back; one device thread serves the
    queue in arrival order, one call at a time, as ``serve(*args)`` (a
    method of the manager's own copy of the worker function).  The
    device thread touches no connection, so a worker process that dies
    with a request outstanding ends its link thread and never blocks
    the owner.  A ``serve`` that raises is reported to the worker, which
    raises it in its task.

    With a ``tracer`` each call is one ``device.serve`` span on
    :data:`OWNER_TRACK` under the requesting task id, with the request's
    counters and ``queue_s`` (arrival to the start of the call),
    ``bytes_in`` and ``bytes_out`` (the arrays received and returned).
    """

    def __init__(self, serve: Callable[..., Any]):
        self._serve = serve
        self._queue: "queue.Queue" = queue.Queue()
        self.tracer = None
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="device-owner")
        self._thread.start()

    def link(self, worker_id: str, conn) -> None:
        """Serve the requests that arrive on ``conn``."""
        threading.Thread(target=self._link, args=(conn,), daemon=True,
                         name=f"device-link-{worker_id}").start()

    def _link(self, conn) -> None:
        reply: "queue.Queue" = queue.Queue(maxsize=1)
        with conn:
            while True:
                try:
                    request = conn.recv()
                except (EOFError, OSError):
                    return              # the worker process is gone
                self._queue.put((time.monotonic(), request, reply))
                try:
                    conn.send(reply.get())
                except OSError:         # it went while its call ran
                    return

    def _run(self) -> None:
        while True:
            item = self._queue.get()
            if item is None:
                return
            arrived, (task_id, args, counters), reply = item
            t0 = time.monotonic()
            try:
                out = ("ok", self._serve(*args))
            except Exception:           # raised again in the worker
                out = ("error", traceback.format_exc())
            tr = self.tracer
            if tr is not None:
                t1 = time.monotonic()
                tr.emit(t0, t1 - t0, "device.serve", "task", OWNER_TRACK,
                        task_id, dict(
                            counters or {}, queue_s=t0 - arrived,
                            bytes_in=_nbytes(args),
                            bytes_out=(_nbytes(out[1]) if out[0] == "ok"
                                       else 0)))
            reply.put(out)

    def close(self) -> None:
        """Stop the device thread once the queued calls are served."""
        self._queue.put(None)
        self._thread.join(timeout=5.0)


class DeviceClient:
    """A worker process's line to the :class:`DeviceOwner`:
    ``client(args, counters)`` sends one request and returns the owner's
    reply.  The request names the task that ``ring`` (the process's own
    tracer) is bound to, or None in an untraced message."""

    def __init__(self, conn, ring):
        self._conn = conn
        self._ring = ring

    def __call__(self, args: tuple, counters: Optional[dict] = None):
        self._conn.send((self._ring.bound_task(), args, counters))
        status, out = self._conn.recv()
        if status != "ok":
            raise RuntimeError(f"the device owner's call raised:\n{out}")
        return out


class _ProcessRing:
    """A worker process's own span ring: attached to the worker function
    for a traced message, emptied into the message's reply."""

    def __init__(self, fn):
        from repro.obs.tracer import Tracer
        self.tracer = Tracer()
        self._attach = getattr(fn, "attach_tracer", None)
        self._on = False

    def begin(self, traced: bool):
        """The tracer to bind for one message: the ring, or None."""
        if self._attach is not None and traced != self._on:
            self._attach(self.tracer if traced else None)
        self._on = traced
        return self.tracer if traced else None

    def take(self) -> tuple:
        events = tuple(self.tracer.events)
        self.tracer.clear()
        return events


def _forbid_jax_backend() -> None:
    """Make this worker process's first JAX backend initialisation raise
    (:data:`NO_BACKEND`); raise now if one is already live."""
    try:
        import jax
        from jax._src import xla_bridge
    except ImportError:
        return
    if xla_bridge.backends_are_initialized():
        raise RuntimeError(f"worker process {os.getpid()} has a JAX "
                           f"backend of its own before its first task")
    jax.config.update("jax_platforms", NO_BACKEND)


def _manager_holds_tpu() -> bool:
    """Has this process initialised JAX on a TPU?  (Never initialises.)"""
    jax = sys.modules.get("jax")
    if jax is None:
        return False
    from jax._src import xla_bridge
    return (xla_bridge.backends_are_initialized()
            and jax.default_backend() == "tpu")


def _process_worker_main(worker_id, inbox, mgr_queue, fn, kwargs, conn,
                         no_backend: bool) -> None:
    if no_backend:
        _forbid_jax_backend()
    ring = _ProcessRing(fn)
    if conn is not None:
        fn.attach_device(DeviceClient(conn, ring.tracer))
    worker_loop(worker_id, inbox, mgr_queue.put, fn, ring=ring, **kwargs)


def _default_start_method() -> str:
    """Pick a safe multiprocessing start method.

    ``fork`` is the cheap default, but forking a process whose XLA client
    is already live deadlocks the child (runtime threads + locks do not
    survive fork).  If a jax backend has been initialized, pay the spawn
    cost instead: the workers re-import the program, and a worker of a
    manager that holds a TPU initialises no backend of its own
    (:class:`WorkerPool`).
    """
    methods = multiprocessing.get_all_start_methods()
    if "fork" not in methods:
        return methods[0]
    if sys.modules.get("jax") is not None:
        try:
            from jax._src import xla_bridge
            if xla_bridge.backends_are_initialized():
                return "spawn"
        except Exception:
            return "spawn"   # can't tell -> be safe
    return "fork"


class WorkerPool(_WorkerSettings):
    """Worker processes launched once and kept across jobs.

    A triples-mode job launches its processes once; so does a pool, for
    a driver that runs many jobs of one worker function
    (``run_job(..., backend="processes", pool=pool)``), so that a job
    measures its messages and not process start-up.  Without a pool the
    processes backend launches one for the job and closes it at the
    end.  :meth:`start` launches the workers not running: all of them
    the first time, later the replacement of a worker that died, with a
    fresh inbox.  :attr:`pids` names each worker's process, so that its
    memory slot can be watched.

    A worker function with a ``serve_device`` method gets a
    :class:`DeviceOwner` in this (the manager's) process: each worker
    process's copy gets a :class:`DeviceClient` through its
    ``attach_device`` and does the host work, and the owner runs every
    device call by the manager's own copy's ``serve_device``.  Such
    workers start by ``spawn``.  They, and every worker of a manager
    that holds a TPU, initialise no JAX backend: the first call that
    would raises (:data:`NO_BACKEND`), so no worker process takes the
    chip or falls back to the CPU and its kernel interpreter.
    """

    def __init__(self, n_workers: int, fn: Callable[[Task], Any], *,
                 batch_fn: Optional[BatchFn] = None,
                 mp_context: Optional[str] = None, **settings):
        if batch_fn is None:
            batch_fn = getattr(fn, "process_batch", None)
        super().__init__(n_workers, fn, batch_fn=batch_fn, **settings)
        self._serve = getattr(fn, "serve_device", None)
        self._method = mp_context
        self._ctx = None
        self._mgr_inbox = None
        self._inboxes: dict[str, Any] = {}
        self._procs: dict[str, Any] = {}
        self.owner: Optional[DeviceOwner] = None
        #: Jobs begun on the pool (:class:`ProcessTransport`).
        self.jobs = 0
        self._closed = False

    @property
    def fn(self) -> Callable[[Task], Any]:
        """The worker function the processes were launched with."""
        return self._fn

    @property
    def heartbeat_interval(self) -> Optional[float]:
        return self._heartbeat_interval

    @property
    def pids(self) -> dict[str, int]:
        """Worker id -> the pid of its running process."""
        return {wid: p.pid for wid, p in self._procs.items()}

    def start(self) -> "WorkerPool":
        """Launch every worker that is not running; returns the pool."""
        if self._closed:
            raise RuntimeError("the worker pool is closed")
        todo = [wid for wid in self.worker_ids if not self.alive(wid)]
        if not todo:
            return self
        if self._ctx is None:
            self._ctx = multiprocessing.get_context(
                self._method or ("spawn" if self._serve is not None
                                 else _default_start_method()))
            self._mgr_inbox = self._ctx.Queue()
        no_backend = self._serve is not None or _manager_holds_tpu()
        links = []
        for wid in todo:
            self._inboxes[wid] = self._ctx.Queue()
            here = there = None
            if self._serve is not None:
                here, there = self._ctx.Pipe()
            p = self._ctx.Process(
                target=_process_worker_main, name=f"worker-{wid}",
                args=(wid, self._inboxes[wid], self._mgr_inbox, self.fn,
                      self._worker_kwargs(wid), there, no_backend),
                daemon=True)
            p.start()
            if there is not None:
                # The child's end lives in the child alone, so its death
                # is an end of file (or a broken pipe) at the link.
                there.close()
                links.append((wid, here))
            self._procs[wid] = p
        if links:
            if self.owner is None:
                self.owner = DeviceOwner(self._serve)
            for wid, here in links:
                self.owner.link(wid, here)
        return self

    def alive(self, worker_id: str) -> bool:
        p = self._procs.get(worker_id)
        return p is not None and p.is_alive()

    def send(self, worker_id: str, msg: Message) -> None:
        self._inboxes[worker_id].put(msg)

    def recv_nowait(self) -> Optional[Message]:
        if self._mgr_inbox is None:
            return None
        try:
            return self._mgr_inbox.get_nowait()
        except queue.Empty:
            return None

    def close(self, timeout: float = 5.0) -> None:
        """Shut the workers down (idempotent): SHUTDOWN to each, join
        while draining their messages, terminate what is left."""
        if self._closed:
            return
        self._closed = True
        for inbox in self._inboxes.values():
            try:
                inbox.put(Message(MessageKind.SHUTDOWN, "manager"))
            except (ValueError, OSError):  # queue already closed
                pass
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline and any(
                p.is_alive() for p in self._procs.values()):
            # A worker exits once its queued messages are written.
            while self.recv_nowait() is not None:
                pass
            for p in self._procs.values():
                p.join(timeout=0.02)
        for p in self._procs.values():
            if p.is_alive():
                p.terminate()
                p.join(timeout=1.0)
        if self.owner is not None:
            self.owner.close()

    def __enter__(self) -> "WorkerPool":
        return self.start()

    def __exit__(self, *exc) -> bool:
        self.close()
        return False


class ProcessTransport(Transport):
    """One OS process per worker (the paper's NPPN placement, for real):
    one job on a :class:`WorkerPool`, the ``pool`` given (left running)
    or one of its own (closed when the job stops).

    Messages are pickled over ``multiprocessing`` queues, so task results
    return in DONE messages rather than via shared memory — a dead worker
    loses exactly its unreported in-flight work, nothing else.  Each
    ASSIGN carries the job's number, and a reply of an earlier job on
    the pool is dropped.  With a ``tracer``, ASSIGNs ask for the
    workers' stage spans, which join the tracer's ring, and the pool's
    device owner records its spans there.
    """

    def __init__(self, n_workers: int, fn: Callable[[Task], Any], *,
                 pool: Optional[WorkerPool] = None,
                 tracer: Optional[Any] = None, **settings):
        self._own = pool is None
        if pool is None:
            pool = WorkerPool(n_workers, fn, **settings)
        elif settings or fn is not pool.fn \
                or n_workers != len(pool.worker_ids):
            raise ValueError(
                "a job on a WorkerPool runs the pool's own function on "
                "its workers, with the settings it was launched with")
        self._pool = pool
        self.worker_ids = list(pool.worker_ids)
        self._tracer = tracer
        self._job = 0
        self._stopped = False

    def start(self) -> None:
        pool = self._pool.start()
        pool.jobs += 1
        self._job = pool.jobs
        if pool.owner is not None:
            pool.owner.tracer = self._tracer

    def send(self, worker_id: str, msg: Message) -> None:
        self._pool.send(worker_id, dataclasses.replace(
            msg, trace=self._tracer is not None, job=self._job))

    def recv_nowait(self) -> Optional[Message]:
        while True:
            msg = self._pool.recv_nowait()
            if msg is None:
                return None
            if msg.kind is not MessageKind.HEARTBEAT \
                    and msg.job != self._job:
                continue            # a late reply of an earlier job
            if msg.events and self._tracer is not None:
                self._tracer.extend(msg.events)
            return msg

    def stop(self) -> None:
        if self._stopped:
            return
        self._stopped = True
        if self._pool.owner is not None:
            self._pool.owner.tracer = None
        if self._own:
            self._pool.close()

    def worker_alive(self, worker_id: str) -> bool:
        return self._pool.alive(worker_id)


TRANSPORTS = {"threads": ThreadTransport, "processes": ProcessTransport}
