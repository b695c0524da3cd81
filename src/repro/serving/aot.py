"""AOT compilation cache + compile accounting for the serving hot path.

Privado (PAPERS.md) specializes inference binaries ahead of time so the
enclave never pays runtime compilation; MaxText's mlperf harness does the
jax equivalent by AOT-compiling the generate step and every prefill bucket
at warmup. This module is the machinery behind ``ServingEngine.warmup()``
(DESIGN.md §AOT warmup & chunked prefill):

* ``CompileMonitor`` — a process-wide compile counter. It listens to
  jax's public ``jax.monitoring`` duration event for backend compilation
  (``/jax/core/compile/backend_compile_duration``, recorded around every
  XLA compile or persistent-cache load), so "zero new compilations after
  warmup" is *asserted against the runtime*, not inferred from our own
  bookkeeping.
* ``AotFn`` — one managed jitted function. ``warm(*args)`` runs
  ``fn.lower(*args).compile()`` and records the call signature (flattened
  leaf avals + treedef). Dispatch mode:

  - ``"compiled"`` (single-device backends): calls route through the
    stored ``Compiled`` executables — measured on this jax version, that
    is the ONLY post-``lower().compile()`` path that performs zero
    further backend compiles (the jit wrapper's executable cache is NOT
    populated by AOT compilation; its first call pays a fresh
    ``backend_compile`` even though the lowering is reused).
  - ``"jit"`` (pipelined backends): ``Compiled`` objects reject inputs
    whose *sharding* differs from the lowering example, and shard_map
    state arrays change sharding between the first and steady-state call
    — so warm() additionally executes the jit wrapper once to seed its
    (shape, sharding)-keyed dispatch cache, and calls stay on the C++
    fast path.

  Either way, a signature first seen after ``AotRegistry.freeze()`` is a
  **compile stall**: recorded with the function name and shapes, surfaced
  in ``ServingEngine.stats()["compile_stalls"]``, and fatal in tests/CI.
* ``AotRegistry`` — the per-engine collection of AotFns plus the
  freeze-time monitor baseline (counters are process-global, so each
  engine snapshots its own zero point). ``wrap(name, fn)`` jits ``fn``
  under its ledger name, so the XLA module (and a profiler trace) names
  the program ``jit_<name>``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax


BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileMonitor:
    """Process-global XLA compile counter fed by a ``jax.monitoring``
    listener. ``install()`` is idempotent; the counter is monotonic for the
    process lifetime (consumers snapshot baselines, never reset)."""

    def __init__(self):
        self.installed = False
        self.backend_compiles = 0       # XLA compilations (incl. cache loads)

    def _on_duration(self, event: str, duration: float, **kw) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.backend_compiles += 1

    def install(self) -> None:
        if not self.installed:
            jax.monitoring.register_event_duration_secs_listener(
                self._on_duration)
            self.installed = True


#: one monitor per process — jax's listener registry is global state
MONITOR = CompileMonitor()


def _sig_of(args) -> Tuple:
    """Hashable call signature: treedef + per-leaf (shape, dtype, weak)."""
    leaves, treedef = jax.tree.flatten(args)
    avals = []
    for x in leaves:
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            avals.append((tuple(x.shape), str(x.dtype),
                          bool(getattr(x, "weak_type", False))))
        else:                            # python scalar -> weak type
            avals.append(((), type(x).__name__, True))
    return treedef, tuple(avals)


def _named(fn: Callable, name: str) -> Callable:
    """``fn`` under the function name ``name``, which jax gives the XLA
    module it lowers to (``jit_<name>``)."""
    def named(*args, **kwargs):
        return fn(*args, **kwargs)
    named.__name__ = named.__qualname__ = name
    named.__wrapped__ = fn              # jax reads the signature through it
    return named


@dataclasses.dataclass
class CompileStall:
    """A managed function called with a signature never warmed."""

    name: str
    sig: Tuple
    frozen: bool                        # True: occurred after freeze()

    def describe(self) -> str:
        shapes = [s for s, _, _ in self.sig[1]]
        return f"{self.name}{shapes}"


class AotFn:
    """One jitted function under AOT management (see module docstring)."""

    def __init__(self, name: str, fn: Callable, registry: "AotRegistry",
                 dispatch: str = "compiled"):
        assert dispatch in ("compiled", "jit"), dispatch
        self.name, self.fn = name, fn
        self.registry = registry
        self.dispatch = dispatch
        self.compiled: Dict[Tuple, Any] = {}    # sig -> stages.Compiled

    @property
    def signatures(self) -> List[Tuple]:
        return list(self.compiled)

    def warm(self, *args):
        """``lower().compile()`` this signature (and, in jit-dispatch mode,
        execute once to seed the sharding-aware dispatch cache). Returns the
        executed output in jit mode, None in compiled mode (callers chain
        state through __call__ during the warm traffic pass)."""
        sig = _sig_of(args)
        if sig not in self.compiled:
            self.compiled[sig] = self.fn.lower(*args).compile()
        if self.dispatch == "jit":
            return self.fn(*args)
        return None

    def __call__(self, *args):
        sig = _sig_of(args)
        if sig not in self.compiled:
            self.registry.record_stall(self, sig)
            self.compiled[sig] = self.fn.lower(*args).compile()
            if self.dispatch == "jit":
                return self.fn(*args)
        if self.dispatch == "jit":
            return self.fn(*args)
        return self.compiled[sig](*args)


class AotRegistry:
    """Per-engine ledger of managed functions + freeze-time baseline."""

    def __init__(self, monitor: Optional[CompileMonitor] = None):
        self.monitor = monitor or MONITOR
        self.fns: Dict[str, AotFn] = {}
        self.frozen = False
        self._baseline: Optional[int] = None
        self.stalls: List[CompileStall] = []

    def wrap(self, name: str, fn: Callable,
             dispatch: str = "compiled") -> AotFn:
        """Manage ``fn`` (a plain function, jitted here) as ``name``."""
        f = AotFn(name, jax.jit(_named(fn, name)), self, dispatch=dispatch)
        self.fns[name] = f
        return f

    def record_stall(self, fn: AotFn, sig: Tuple) -> None:
        self.stalls.append(CompileStall(fn.name, sig, self.frozen))

    def freeze(self) -> None:
        """Warmup done: snapshot the monitor so ``post_freeze_compiles``
        counts only what happens during steady-state serving."""
        self.monitor.install()
        self.frozen = True
        self._baseline = self.monitor.backend_compiles

    @property
    def post_freeze_compiles(self) -> Optional[int]:
        """XLA compiles since freeze() — None only when never frozen.
        NOTE: process-global; another engine warming up after this one
        froze shows up here."""
        if self._baseline is None:
            return None
        return self.monitor.backend_compiles - self._baseline

    @property
    def post_freeze_stalls(self) -> List[CompileStall]:
        return [s for s in self.stalls if s.frozen]
