"""s4u::Engine equivalent: simulation setup and run.

Reference: /root/reference/src/s4u/s4u_Engine.cpp — load_platform,
register_function, load_deployment, run, clock; plus --cfg command-line
handling (sg_config.cpp).
"""

from __future__ import annotations

import xml.etree.ElementTree as ET
from typing import Callable, Dict, List, Optional

from ..exceptions import ParseError
from ..kernel.engine import EngineImpl
from ..models.registry import setup_models
from ..ops import opstats
from ..platform.xml import PlatformLoader
from ..utils.config import config
from ..utils import log as _xlog

#: deployment warnings (ActorImpl::start / sg_platf's catch)
_deploy_log = _xlog.get_category("simix_process")
from ..utils.signal import Signal


class Engine:
    _instance: Optional["Engine"] = None

    on_platform_created = EngineImpl.on_platform_created
    on_simulation_end = EngineImpl.on_simulation_end
    on_time_advance = EngineImpl.on_time_advance
    on_deadlock = EngineImpl.on_deadlock

    def __init__(self, argv: Optional[List[str]] = None):
        # Replacing the engine singleton retires the previous engine: its
        # signal subscriptions must not fire into this one (same guarantee
        # as _reset, for code that constructs engines back-to-back).
        if Engine._instance is not None:
            Engine._instance.pimpl.disconnect_signals()
        # --cfg must land BEFORE the kernel comes up: EngineImpl's
        # ContextFactory freezes contexts/stack-size at creation
        # (reference order too: sg_config runs first, sg_config.cpp)
        if argv:
            rest = config.parse_argv(argv[1:])
            argv[1:] = rest
        self.pimpl = EngineImpl()
        self._registered_functions: Dict[str, Callable] = {}
        self._default_function: Optional[Callable] = None
        self._models_ready = False
        Engine._instance = self

    # -- singletons --------------------------------------------------------
    @classmethod
    def get_instance(cls) -> "Engine":
        if cls._instance is None:
            cls._instance = Engine(["simgrid_tpu"])
        return cls._instance

    @classmethod
    def _reset(cls) -> None:
        """Tear down the process-wide simulation state so a fresh Engine can
        be created (mainly for test harnesses; one engine per process in
        normal use, like the reference)."""
        from ..kernel import profile as profile_mod
        from ..utils import log as _xlog
        from .mailbox import Mailbox
        from .. import instr
        instr.stop()
        # drop the dead engine's log context closures (they pin the
        # whole platform in memory and would render stale actor info)
        _xlog.clock_getter = None
        _xlog.actor_info_getter = None
        if cls._instance is not None:
            cls._instance.pimpl.disconnect_signals()
            cls._instance.pimpl.shutdown_contexts()
        cls._instance = None
        EngineImpl.instance = None
        Mailbox._instances.clear()
        profile_mod.clear_trace_registry()

    @property
    def clock(self) -> float:
        return self.pimpl.now

    @classmethod
    def get_clock(cls) -> float:
        return cls.get_instance().pimpl.now

    # -- configuration -----------------------------------------------------
    def set_config(self, option: str, value=None) -> None:
        if value is None:
            config.set_from_string(option)
        else:
            config.set(option, value)

    # -- platform ----------------------------------------------------------
    def _ensure_models(self) -> None:
        if not self._models_ready:
            setup_models(self.pimpl)
            self._models_ready = True

    def load_platform(self, path: str) -> None:
        self._ensure_models()
        with opstats.span("platform.load"):
            PlatformLoader(self.pimpl).load(path)
        # TRACE_start fires on platform creation in the reference
        # (instr_config.cpp:297); same here so actors created before
        # run() are captured.
        if config["tracing"]:
            from .. import instr
            instr.start(self.pimpl)

    def create_root_zone(self, name: str, routing: str = "Full"):
        """Programmatic platform building entry."""
        self._ensure_models()
        from ..platform.xml import _make_zone
        return _make_zone(self.pimpl, None, name, routing)

    # -- actors ------------------------------------------------------------
    def register_function(self, name: str, code: Callable) -> None:
        self._registered_functions[name] = code

    def register_default(self, code: Callable) -> None:
        self._default_function = code

    def load_deployment(self, path: str) -> None:
        """Start actors from a deployment XML (reference
        surf_parse deployment: <actor>/<process> with <argument> children)."""
        from .actor import Actor
        try:
            tree = ET.parse(path)
        except ET.ParseError as e:
            raise ParseError(f"{path}: {e}") from None
        for elem in tree.getroot():
            if elem.tag not in ("actor", "process"):
                continue
            host_name = elem.get("host")
            func_name = elem.get("function")
            host = self.host_by_name(host_name)
            code = self._registered_functions.get(func_name,
                                                  self._default_function)
            assert code is not None, f"Function '{func_name}' unknown"
            args = [child.get("value") for child in elem
                    if child.tag == "argument"]
            props = {child.get("id"): child.get("value")
                     for child in elem if child.tag == "prop"}
            start_time = float(elem.get("start_time", "0"))
            kill_time = float(elem.get("kill_time", "-1"))
            on_failure = elem.get("on_failure", "DIE")

            auto_restart = on_failure != "DIE"
            # every deployment actor joins its host's boot list
            # (sg_platf.cpp:447: unconditional emplace); turn_off
            # prunes non-restart entries, turn_on reboots the rest
            host.actors_at_boot.append(
                {"name": func_name, "code": code, "args": args,
                 "kill_time": kill_time, "auto_restart": auto_restart})

            def launch(code=code, args=args, host=host, name=func_name,
                       kill_time=kill_time, auto_restart=auto_restart,
                       props=props):
                if not host.is_on():
                    # ActorImpl::start + sg_platf's catch around it;
                    # the failed creation still consumed a PID (the
                    # ActorImpl was built before start() threw)
                    self.pimpl.next_pid()
                    _deploy_log.warning(
                        "Cannot launch actor '%s' on failed host '%s'"
                        % (name, host.name))
                    _deploy_log.warning(
                        "Deployment includes some initially turned off "
                        "Hosts ... nevermind.")
                    return None
                actor = Actor.create(name, host, code, *args)
                if props:
                    actor.pimpl.properties.update(props)
                if kill_time >= 0:
                    actor.set_kill_time(kill_time)
                if auto_restart:
                    actor.pimpl.auto_restart = True
                return actor

            if start_time > 0:
                self.pimpl.timer_set(start_time, launch)
            else:
                launch()

    # -- entity lookup -----------------------------------------------------
    def host_by_name(self, name: str):
        host = self.pimpl.hosts.get(name)
        assert host is not None, f"Host '{name}' not found"
        return host

    def host_by_name_or_null(self, name: str):
        return self.pimpl.hosts.get(name)

    def get_all_hosts(self) -> List:
        # name-sorted like the reference (its host registry is a
        # std::map, Engine::get_all_hosts iterates in name order — the
        # token-ring tesh oracle pins the resulting actor placement)
        return [h for _, h in sorted(self.pimpl.hosts.items())]

    def get_host_count(self) -> int:
        return len(self.pimpl.hosts)

    def link_by_name(self, name: str):
        link = self.pimpl.links.get(name)
        assert link is not None, f"Link '{name}' not found"
        return link

    def get_all_links(self) -> List:
        return list(self.pimpl.links.values())

    def get_netzone_root(self):
        return self.pimpl.netzone_root

    def netpoint_by_name(self, name: str):
        return self.pimpl.netpoints.get(name)

    def get_all_netpoints(self) -> List:
        return list(self.pimpl.netpoints.values())

    # -- run ---------------------------------------------------------------
    def run_until(self, date: float) -> None:
        """Advance the simulation up to `date` and pause (the kernel
        state stays live; call run()/run_until() again to continue)."""
        if config["tracing"]:
            from .. import instr
            instr.start(self.pimpl)
        self.pimpl.run(until=date)

    def run(self) -> None:
        if config["tracing"]:
            from .. import instr
            instr.start(self.pimpl)
        self.pimpl.run()


def get_clock() -> float:
    return Engine.get_clock()
