"""Plugin manager: manifest loading, module registry, lifecycle pump.

Reference equivalent: NFCPluginManager — loads Plugin.xml, dlopens each
plugin, drives the 9-phase lifecycle, lets modules find each other via
FindModule<T>(), and supports hot reload (NFCPluginManager.cpp:60-327,
211-300).  Here a plugin is a Python module exposing `create_plugin(pm)`
returning a `Plugin`; "dlopen" is importlib, and hot reload is
importlib.reload + phase recompilation.  The per-frame `run_once()` mirrors
the host side of the reference main loop (NFPluginLoader.cpp:250-273): pump
each module's host `execute()`, then run the compiled device tick once.
"""

from __future__ import annotations

import dataclasses
import importlib
import time
import xml.etree.ElementTree as ET
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Type, TypeVar

from .kernel import Kernel
from .module import LIFECYCLE, SHUTDOWN, Module
# after .module: telemetry's package imports kernel.module back
from ..telemetry.tracing import span

M = TypeVar("M", bound=Module)


class Plugin:
    """A named group of modules installed/uninstalled together."""

    def __init__(self, name: str, modules: Sequence[Module] = ()):
        self.name = name
        self.modules: List[Module] = list(modules)

    def add(self, module: Module) -> Module:
        self.modules.append(module)
        return module


class PluginManager:
    def __init__(self, app_id: int = 1, app_name: str = "app"):
        self.app_id = app_id
        self.app_name = app_name
        self.plugins: Dict[str, Plugin] = {}
        self._plugin_sources: Dict[str, str] = {}  # plugin name -> import path
        self.modules: Dict[str, Module] = {}
        self.kernel: Optional[Kernel] = None
        self._started = False
        self.frame = 0

    # -- registration -------------------------------------------------------

    def register_plugin(self, plugin: Plugin, source: str = "") -> Plugin:
        if plugin.name in self.plugins:
            raise ValueError(f"plugin {plugin.name!r} already registered")
        self.plugins[plugin.name] = plugin
        if source:
            self._plugin_sources[plugin.name] = source
        for m in plugin.modules:
            self._register_module(m)
        return plugin

    def _register_module(self, m: Module) -> None:
        if m.name in self.modules:
            raise ValueError(f"module {m.name!r} already registered")
        self.modules[m.name] = m
        if isinstance(m, Kernel):
            self.kernel = m
            for other in self.modules.values():
                other.kernel = m
        m.kernel = self.kernel

    def load_plugin_module(self, import_path: str) -> Plugin:
        """Import a python module and install its plugin (the dlopen +
        DllStartPlugin equivalent)."""
        mod = importlib.import_module(import_path)
        plugin = mod.create_plugin(self)
        return self.register_plugin(plugin, source=import_path)

    def load_manifest(self, path: Path) -> int:
        """Load a Plugin.xml-format manifest: <XML><Plugin Name="pkg.mod"/>
        ... (reference _Out/Debug/Plugin.xml)."""
        root = ET.parse(str(path)).getroot()
        n = 0
        for p in root.findall("Plugin"):
            self.load_plugin_module(p.get("Name", ""))
            n += 1
        return n

    def find_module(self, cls: Type[M]) -> M:
        """FindModule<T>: locate the registered instance of a module type
        (the seam all cross-module links go through)."""
        for m in self.modules.values():
            if isinstance(m, cls):
                return m  # type: ignore[return-value]
        raise KeyError(f"no module of type {cls.__name__} registered")

    def find_module_by_name(self, name: str) -> Module:
        return self.modules[name]

    # -- lifecycle ----------------------------------------------------------

    def _each(self, phase: str) -> None:
        for m in self.modules.values():
            getattr(m, phase)()

    def start(self) -> None:
        """awake → init → (kernel.build) → after_init → check_config →
        ready_execute → (compile).  Modules declare schemas and timers in
        init; the world is built before after_init so that phase can create
        seed objects."""
        if self._started:
            return
        self._each("awake")
        self._each("init")
        if self.kernel is not None:
            self.kernel.build(list(self.modules.values()))
        self._each("after_init")
        self._each("check_config")
        self._each("ready_execute")
        if self.kernel is not None:
            self.kernel.compile()
        self._started = True

    def execute_modules(self) -> None:
        """Host execute() on every module, the kernel's own last: the one
        place a frame's host modules run (run_once, GameRole.execute)."""
        with span("tick.modules"):
            for m in self.modules.values():
                if m is not self.kernel:
                    with span("module." + m.name):
                        m.execute()
            if self.kernel is not None:
                with span("module." + self.kernel.name):
                    self.kernel.execute()

    def run_once(self) -> None:
        """One frame: host execute() on every module, then the device tick."""
        self.execute_modules()
        if self.kernel is not None:
            self.kernel.tick()
        self.frame += 1

    def run(self, frames: int) -> None:
        for _ in range(frames):
            self.run_once()

    def shutdown(self) -> None:
        for phase in SHUTDOWN:
            self._each(phase)
        self._started = False

    # -- hot reload ---------------------------------------------------------

    def reload_plugin(self, name: str) -> Plugin:
        """Live-patch one plugin (reference ReLoadPlugin): shut its modules,
        re-import the source, re-install, rebuild the phase list and force
        recompilation of the tick.  World state is preserved."""
        source = self._plugin_sources.get(name)
        if source is None:
            raise KeyError(f"plugin {name!r} was not loaded from an import path")
        old = self.plugins.pop(name)
        for m in old.modules:
            m.before_shut()
            m.shut()
            self.modules.pop(m.name, None)
        mod = importlib.reload(importlib.import_module(source))
        plugin = mod.create_plugin(self)
        self.register_plugin(plugin, source=source)
        for m in plugin.modules:
            m.awake()
            m.init()
            m.after_init()
            m.ready_execute()
        if self.kernel is not None:
            # every module (including the kernel) contributes its own phases
            # exactly once; stale phases from the unloaded plugin are gone
            self.kernel.set_phases(
                [p for m in self.modules.values() for p in m.phases]
            )
            self.kernel.compile()
        return plugin
