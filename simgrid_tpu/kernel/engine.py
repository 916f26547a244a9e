"""The simulation engine: maestro event loop + time advance.

Re-implements the reference's deterministic scheduling loop
(SIMIX_run, src/simix/smx_global.cpp:377-529) and time-advance
(surf_solve, src/surf/surf_c_bindings.cpp:45-151): run scheduling
sub-rounds until no actor is runnable, handle simcalls in FIFO order, jump
simulated time to the next action completion (the min-reduction over
models, solved by the LMM backend), apply profile events, update action
states and wake finished/failed activities.
"""

from __future__ import annotations

import heapq
import weakref as _weakref
from typing import Callable, Dict, List, Optional

from ..exceptions import SimgridException
from ..ops import opstats
from ..utils import log as _log
from ..utils.config import config
from ..utils.signal import Signal
from .actor import ActorImpl
from .context import ContextFactory
from .profile import FutureEvtSet
from .activity import MailboxImpl

_logger = _log.get_category("kernel")


class Timer:
    """A host-side timer fired at an absolute simulated date
    (reference simix::Timer, smx_global.cpp:120-146)."""

    _cancelled = False

    def __init__(self, date: float, callback: Callable[[], None]):
        self.date = date
        self.callback = callback

    def remove(self) -> None:
        self._cancelled = True


class EngineImpl:
    """Kernel singleton: owns models, actors, mailboxes, timers, clock."""

    instance: Optional["EngineImpl"] = None

    on_time_advance = Signal()
    on_platform_created = Signal()
    on_simulation_end = Signal()
    on_deadlock = Signal()

    def __init__(self):
        EngineImpl.instance = self
        self.now = 0.0
        self.advances = 0                 # surf_solve calls so far
        self.models: List = []            # all_existing_models
        self.host_model = None
        self.cpu_model = None
        self.network_model = None
        self.storage_model = None
        self.vm_model = None
        self.future_evt_set = FutureEvtSet()
        self.watched_hosts: set = set()

        self.context_factory = ContextFactory()
        self._pid = 1        # maestro takes pid 0 below; users start at 1
        self._mc_seq = 0
        #: weakrefs to mutex/semaphore/condvar impls, for MC snapshots
        self.mc_sync_objects: list = []
        #: actor-noted MC-relevant state, (pid, key) -> value
        self.mc_notes: dict = {}
        self.maestro = ActorImpl(self, "maestro", None)
        self.maestro.pid = 0
        self._pid = 1        # maestro consumed pid 1; reclaim it
        self.actors_to_run: List[ActorImpl] = []
        self.actors_terminated_pending: List[ActorImpl] = []
        self.actors_that_ran: List[ActorImpl] = []
        self.process_list: Dict[int, ActorImpl] = {}
        self.actors_to_destroy: List[ActorImpl] = []
        self.daemons: List[ActorImpl] = []
        self.tasks: List[Callable[[], None]] = []
        self._timers: List = []  # heap of (date, seq, Timer)
        self._timer_seq = 0
        self.mailboxes: Dict[str, MailboxImpl] = {}
        self.netpoints: Dict[str, object] = {}
        self.hosts: Dict[str, object] = {}
        self.links: Dict[str, object] = {}
        self.storages: Dict[str, object] = {}
        self.netzone_root = None
        self._breakpoint = -1.0
        # (signal, fn) pairs auto-disconnected on engine teardown: models
        # and plugins hook class-level signals through here so a dead
        # engine's callbacks never fire into a fresh engine (the reference
        # installs its hooks once per process, network_ib.cpp:17-54; we
        # support many engines per process for tests/MC branches).
        self._signal_connections: List = []
        _log.clock_getter = lambda: self.now

        def actor_info():
            actor = self.context_factory.current_actor
            if actor is None:
                return (0, "maestro", "")
            return (actor.pid, actor.name,
                    actor.host.name if actor.host else "")
        _log.actor_info_getter = actor_info

    # -- engine-scoped signal subscriptions ------------------------------
    def connect_signal(self, signal, fn) -> None:
        """Connect fn to a (class-level) signal for this engine's lifetime."""
        signal.connect(fn)
        self._signal_connections.append((signal, fn))

    def disconnect_signals(self) -> None:
        for signal, fn in self._signal_connections:
            try:
                signal.disconnect(fn)
            except ValueError:
                pass
        self._signal_connections.clear()

    # ------------------------------------------------------------------
    def next_pid(self) -> int:
        pid = self._pid
        self._pid += 1
        return pid

    def next_mc_seq(self) -> int:
        """Deterministic creation counter labeling kernel objects for
        the model checker (stable across MC re-executions)."""
        self._mc_seq += 1
        return self._mc_seq

    def shutdown_contexts(self) -> None:
        """Kill every live actor thread (engine teardown): without
        this, each discarded engine leaks its parked context threads
        and replay-heavy users (the model checker re-executes the
        program hundreds of times) exhaust the OS thread limit."""
        actors = list(self.process_list.values()) + list(self.actors_to_run)
        for actor in actors:
            ctx = getattr(actor, "context", None)
            if ctx is None or ctx._thread is None:
                continue
            if ctx._thread.is_alive():
                ctx.iwannadie = True
                try:
                    ctx._lock.release()
                except RuntimeError:
                    pass     # already released (racing normal handoff)
                ctx._thread.join(timeout=5)
                # the dying actor's stop() released maestro_lock; put it
                # back into the held-by-maestro state
                self.context_factory.maestro_lock.acquire(False)

    def register_mc_object(self, obj) -> tuple:
        """Assign a replay-stable mc_key AND remember the object so
        the state-signature walk (mc/state.py) can serialize every
        live sync object — the role of the reference's snapshot region
        enumeration (sosp/Region), minus the page store."""
        key = (type(obj).__name__, self.next_mc_seq())
        self.mc_sync_objects.append(_weakref.ref(obj))
        return key

    def add_model(self, model) -> None:
        self.models.append(model)

    def mailbox_by_name_or_create(self, name: str) -> MailboxImpl:
        mbox = self.mailboxes.get(name)
        if mbox is None:
            mbox = MailboxImpl(self, name)
            self.mailboxes[name] = mbox
        return mbox

    # -- actor management ------------------------------------------------
    def create_actor(self, name: str, host, code: Callable,
                     daemonize: bool = False) -> ActorImpl:
        if not host.is_on():
            raise SimgridException(
                f"Cannot create actor '{name}' on failed host '{host.name}'")
        actor = ActorImpl(self, name, host, code)
        actor.context = self.context_factory.create_context(code, actor)
        self.process_list[actor.pid] = actor
        self.actors_to_run.append(actor)
        if daemonize:
            actor.daemonize()
        ActorImpl.on_creation(actor)
        return actor

    def actor_terminated(self, actor: ActorImpl) -> None:
        """Called from the actor's context just before its final yield."""
        self.process_list.pop(actor.pid, None)
        if actor in self.daemons:
            self.daemons.remove(actor)
        if actor.host is not None and actor in actor.host.actor_list:
            actor.host.actor_list.remove(actor)
        # Cancel any remaining comms of this actor (kill cleanup).
        for comm in list(actor.comms):
            comm.cancel()
        actor.comms.clear()
        self.actors_terminated_pending.append(actor)
        self.actors_to_destroy.append(actor)

    def actor_crashed(self, actor: ActorImpl, exc: BaseException) -> None:
        _logger.error("Actor %s@%s died of an uncaught exception: %s",
                      actor.name,
                      actor.host.name if actor.host else "?", exc)

    # -- timers ----------------------------------------------------------
    def timer_set(self, date: float, callback: Callable[[], None]) -> Timer:
        timer = Timer(date, callback)
        heapq.heappush(self._timers, (date, self._timer_seq, timer))
        self._timer_seq += 1
        return timer

    def next_timer_date(self) -> float:
        while self._timers and self._timers[0][2]._cancelled:
            heapq.heappop(self._timers)
        return self._timers[0][0] if self._timers else -1.0

    def _execute_timers(self) -> bool:
        result = False
        while self._timers and self.now >= self._timers[0][0]:
            _, _, timer = heapq.heappop(self._timers)
            if timer._cancelled:
                continue
            result = True
            timer.callback()
        return result

    # -- task queue (futures' .then callbacks) ---------------------------
    def add_task(self, task: Callable[[], None]) -> None:
        self.tasks.append(task)

    def _execute_tasks(self) -> bool:
        if not self.tasks:
            return False
        while self.tasks:
            batch, self.tasks = self.tasks, []
            for task in batch:
                task()
        return True

    # ------------------------------------------------------------------
    # surf_solve: the time-advance (surf_c_bindings.cpp:45-151)
    # ------------------------------------------------------------------
    def surf_solve(self, max_date: float) -> float:
        """One time advance, under an ``engine.advance`` span (the
        generic host sweep or the drain fast path, whichever the
        models take: ``native_advances`` / ``fastpath_advances``)."""
        with opstats.span("engine.advance", id=self.advances):
            time_delta = self._advance(max_date)
        self.advances += 1
        return time_delta

    def _advance(self, max_date: float) -> float:
        time_delta = -1.0
        # >= 0: a bound AT the current date (run_until(now), timers at
        # t=0) means a zero-length advance, not an unbounded one
        if max_date >= 0.0:
            assert max_date >= self.now, \
                f"You asked to simulate up to {max_date} but that's in the past"
            time_delta = max_date - self.now

        # Physical models first: host composes cpu+network+storage.
        next_event_phy = self.host_model.next_occurring_event(self.now)
        if (time_delta < 0.0 or next_event_phy < time_delta) and next_event_phy >= 0.0:
            time_delta = next_event_phy
        if self.vm_model is not None:
            next_event_virt = self.vm_model.next_occurring_event(self.now)
            if (time_delta < 0.0 or next_event_virt < time_delta) and next_event_virt >= 0.0:
                time_delta = next_event_virt
        for model in self.models:
            if model in (self.host_model, self.vm_model, self.network_model,
                         self.storage_model, self.cpu_model):
                continue
            next_event_model = model.next_occurring_event(self.now)
            if (time_delta < 0.0 or next_event_model < time_delta) and next_event_model >= 0.0:
                time_delta = next_event_model

        # Stalled-resume upgrade over the reference: if no action can ever
        # complete (time_delta < 0, e.g. every flow parked on a
        # zero-bandwidth link) but actions are running and a future profile
        # event could unblock them, jump to that event instead of
        # deadlocking (the reference bails out here, surf_c_bindings.cpp:
        # 128-134 — its own FIXME admits the availability-0 case is broken).
        if time_delta < 0.0:
            next_event_date = self.future_evt_set.next_date()
            if next_event_date >= 0.0 and any(
                    model.started_action_set for model in self.models):
                time_delta = next_event_date - self.now

        # Consume profile events up to the chosen horizon.
        while True:
            next_event_date = self.future_evt_set.next_date()
            if not self.network_model.next_occurring_event_is_idempotent():
                # ns-3-style co-simulation backend hook
                if next_event_date != -1.0:
                    time_delta = min(next_event_date - self.now, time_delta)
                else:
                    time_delta = max(next_event_date - self.now, time_delta)
                model_next_action_end = self.network_model.next_occurring_event(time_delta)
                if model_next_action_end >= 0.0:
                    time_delta = model_next_action_end
            if next_event_date < 0.0 or next_event_date > self.now + time_delta:
                break
            while True:
                popped = self.future_evt_set.pop_leq(next_event_date)
                if popped is None:
                    break
                event, value, resource = popped
                if value < 0:
                    # Profile idx-0 placeholder (value -1, Profile.cpp:26-31).
                    # The reference applies it anyway (surf_c_bindings.cpp:
                    # 112-125), which is only harmless because conventional
                    # traces start at t=0 and instantly overwrite it; we skip
                    # it so traces starting at t>0 keep the platform value
                    # until their first real event.
                    continue
                if (resource.is_used()
                        or resource.name in self.watched_hosts):
                    time_delta = next_event_date - self.now
                round_start = self.now
                self.now = next_event_date
                resource.apply_event(event, value)
                self.now = round_start

        if time_delta < 0:
            return -1.0

        self.now += time_delta
        for model in self.models:
            model.update_actions_state(self.now, time_delta)
        EngineImpl.on_time_advance(time_delta)
        return time_delta

    def _wake_processes(self) -> None:
        # reference SIMIX_wake_processes (smx_global.cpp:336-356)
        for model in self.models:
            action = model.extract_failed_action()
            while action is not None:
                if action.activity is not None:
                    action.activity.post()
                action = model.extract_failed_action()
            action = model.extract_done_action()
            while action is not None:
                if action.activity is not None:
                    action.activity.post()
                action = model.extract_done_action()

    def _fire_terminations(self) -> None:
        """Fire on_termination from the maestro context (the reference
        runs signal callbacks in the kernel, so e.g. the actor-exiting
        example's lines read "(maestro@) Actor A terminates now")."""
        while self.actors_terminated_pending:
            from .actor import ActorImpl
            ActorImpl.on_termination(self.actors_terminated_pending.pop(0))

    def _empty_trash(self) -> None:
        """Destroy dead actors (reference intrusive-refcount release):
        fired one simulation round AFTER termination — the C++ ActorPtr
        held through the scheduling round keeps the actor alive until
        the next maestro pass (pinned by the actor-exiting oracle)."""
        from .actor import ActorImpl
        while self.actors_to_destroy:
            ActorImpl.on_destruction(self.actors_to_destroy.pop(0))

    # ------------------------------------------------------------------
    # The main loop (SIMIX_run, smx_global.cpp:377-529)
    # ------------------------------------------------------------------
    def run(self, until: float = -1.0) -> None:
        """Run the simulation; with `until` >= 0, pause once the clock
        reaches that date (reference Engine::run_until) leaving the
        kernel state intact so run() can be called again."""
        import sys as _sys
        # Strict lock-pair handoff means at most one simulator thread is
        # ever runnable; a long GIL switch interval removes pointless
        # preemption checks during the ~1M handoffs of a big run
        # (chord-10k: the handoff path was 36% of wall time).  Restored
        # on exit so embedding processes keep the default.
        _prev_interval = _sys.getswitchinterval()
        _sys.setswitchinterval(5.0)
        try:
            self._run_loop(until)
        finally:
            _sys.setswitchinterval(_prev_interval)

    def _presolve(self) -> None:
        """reference surf_presolve (surf_interface.cpp:57-73): apply
        every profile event dated at the simulation start BEFORE the
        first scheduling round, so t=0 profile values (speed_file
        "0 0.5" lines etc.) are already visible to the first actor —
        pinned by the platform-profile oracle's first output line."""
        while True:
            popped = self.future_evt_set.pop_leq(self.now)
            if popped is None:
                break
            event, value, resource = popped
            if value < 0:
                continue    # idx-0 placeholder (see surf_solve)
            resource.apply_event(event, value)

    def _run_loop(self, until: float) -> None:
        time = 0.0
        if not getattr(self, "_presolved", False):
            self._presolved = True
            self._presolve()
        while True:
            self._execute_tasks()

            while self.actors_to_run:
                # Run all ready actors (serial, deterministic order).
                self.context_factory.run_all(self.actors_to_run)
                self.actors_to_run, self.actors_that_ran = \
                    [], self.actors_to_run
                # Answer the simcalls issued during this sub-round, FIFO.
                for actor in self.actors_that_ran:
                    if actor.simcall_.call is not None:
                        actor.simcall_handle()
                self._fire_terminations()
                self._execute_tasks()
                while True:
                    self._wake_processes()
                    if not self._execute_tasks():
                        break
                # Only daemons left: kill them and wrap up.
                if len(self.process_list) == len(self.daemons) and self.daemons:
                    for dmon in list(self.daemons):
                        self.maestro.kill(dmon)

            if until >= 0.0 and self.now >= until:
                return               # already at/past the pause date
            time = self.next_timer_date()
            if until >= 0.0 and (time < 0.0 or time > until):
                time = until
            if time > -1.0 or self.process_list:
                time = self.surf_solve(time)

            again = True
            while again:
                again = self._execute_timers()
                if self._execute_tasks():
                    again = True
                self._wake_processes()

            self._empty_trash()

            if until >= 0.0 and self.now >= until and not self.actors_to_run:
                return               # paused at the requested date

            if not (time > -1.0 or self.actors_to_run):
                break

        if self.process_list:
            if len(self.process_list) <= len(self.daemons):
                _logger.critical(
                    "Daemon actors cannot do any blocking activity once the "
                    "simulation is over.")
            else:
                _logger.critical("Oops! Deadlock or code not perfectly clean.")
            self.display_process_status()
            EngineImpl.on_deadlock()
            raise SimgridException("Deadlock detected: actors are still "
                                   "blocked but no event remains")
        EngineImpl.on_simulation_end()

    def display_process_status(self) -> None:
        _logger.info("%d actors are still active, awaiting something. "
                     "Here is their status:", len(self.process_list))
        for actor in self.process_list.values():
            synchro = actor.waiting_synchro
            what = type(synchro).__name__ if synchro is not None else "nothing"
            detail = ""
            mailbox = getattr(synchro, "mailbox", None)
            if mailbox is None:
                mailbox = getattr(synchro, "mailbox_cpy", None)
            if mailbox is not None:
                detail = f" on mailbox '{mailbox.name}'"
            _logger.info("Actor %d (%s@%s): waiting for %s%s", actor.pid,
                         actor.name,
                         actor.host.name if actor.host else "?", what,
                         detail)
