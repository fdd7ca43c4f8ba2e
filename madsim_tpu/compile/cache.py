"""The process-level step-program cache + the compile observability log.

`PROGRAM_CACHE` maps (backend, structural signature, runner kind) to the
jitted runner callable, so every Runtime whose construction freezes to
the same signature (compile/signature.py) shares ONE Python-level jit
entry — and therefore one trace and one XLA executable per (batch shape,
static chunk length). The chunked/fused runners, `_inject`, the
compacting path, `find_divergence`, and the batched fingerprint jit all
resolve through here; `explore()` rounds, `harness/simtest` tests, and
whole test files stop paying per-Runtime recompiles.

`COMPILE_LOG` is the observability half: runner bodies call
`COMPILE_LOG.note_trace(label, ...)` as their first traced-Python side
effect, so every retrace (= every fresh executable, modulo persistent
compile-cache hits that skip only the XLA stage) is counted and labeled.
When available, `jax.monitoring` duration listeners add real
trace/lower/compile stage timings. Records fan out to any attached
`obs.metrics.SweepObserver` via its `on_compile` hook, and
`COMPILE_LOG.summary()` is what `scripts/ci.sh` prints at suite end.
"""

from __future__ import annotations

import collections
import os
import threading
import time
from typing import Any, Callable


class CompileLog:
    """Process-global compile counter / stage-timing log (thread-safe)."""

    MAX_EVENTS = 1024   # bounded: a long suite must not accumulate RAM

    def __init__(self):
        self._lock = threading.Lock()
        self.traces = collections.Counter()      # label -> retrace count
        self.events = collections.deque(maxlen=self.MAX_EVENTS)
        self.durations = collections.Counter()   # stage -> seconds
        self.degrades: list[dict] = []           # signature degradations
        self._degraded_keys: set = set()
        self._observers: list[Any] = []
        self._t0 = time.time()

    # -- the counter (called from inside traced runner bodies) -----------
    def note_trace(self, label: str, **info) -> None:
        rec = dict(kind="compile", label=label, t=round(
            time.time() - self._t0, 3), **info)
        with self._lock:
            self.traces[label] += 1
            self.events.append(rec)
            observers = list(self._observers)
        for o in observers:
            o.on_compile(rec)

    # -- signature degradations (compile/signature.py) --------------------
    def note_degrade(self, owner: str, cell: str, detail: str = "") -> None:
        """A closure capture froze to an identity token: `owner`'s cell
        `cell` opted its Runtime out of cross-instance program sharing
        (compile/signature.py module docstring). Before r12 this was a
        SILENT cache degrade — cache misses were undiagnosable; now it
        is an observer record (kind="compile",
        label="signature_degrade") and a line in `summary()` — the
        suite-end report scripts/ci.sh prints. De-duplicated per
        (owner, cell): freeze() runs on every construction."""
        rec = dict(kind="compile", label="signature_degrade",
                   owner=owner, cell=cell, detail=detail,
                   t=round(time.time() - self._t0, 3))
        with self._lock:
            if (owner, cell) in self._degraded_keys:
                return
            self._degraded_keys.add((owner, cell))
            self.degrades.append(rec)
            self.events.append(rec)
            observers = list(self._observers)
        for o in observers:
            o.on_compile(rec)

    # -- stage durations (fed by jax.monitoring when available) ----------
    def note_duration(self, stage: str, secs: float) -> None:
        with self._lock:
            self.durations[stage] += secs

    # -- observers (obs.metrics.SweepObserver.on_compile) ----------------
    def attach(self, observer) -> None:
        with self._lock:
            if observer not in self._observers:
                self._observers.append(observer)

    def detach(self, observer) -> None:
        with self._lock:
            if observer in self._observers:
                self._observers.remove(observer)

    # -- reporting --------------------------------------------------------
    def recent(self, n: int = 20) -> list[dict]:
        """The last `n` compile records (what retraced, when) — the
        drill-down behind snapshot()'s counters."""
        with self._lock:
            return list(self.events)[-n:]

    def snapshot(self) -> dict:
        with self._lock:
            return dict(
                traces=dict(self.traces),
                traces_total=sum(self.traces.values()),
                stage_secs={k: round(v, 3)
                            for k, v in self.durations.items()},
                degrades=list(self.degrades),
            )

    def summary(self) -> str:
        s = self.snapshot()
        parts = [f"{n}x {label}" for label, n in
                 sorted(s["traces"].items(), key=lambda kv: -kv[1])]
        stages = " ".join(f"{k}={v:.1f}s"
                          for k, v in sorted(s["stage_secs"].items()))
        deg = s["degrades"]
        deg_s = ""
        if deg:
            who = ", ".join(f"{d['owner']}.{d['cell']}" for d in deg[:6])
            deg_s = (f" | {len(deg)} signature degrade(s) — no cross-"
                     f"Runtime sharing for: {who}"
                     + (" …" if len(deg) > 6 else ""))
        return (f"compile log: {s['traces_total']} trace(s)"
                + (f" [{', '.join(parts)}]" if parts else "")
                + (f" | {stages}" if stages else "")
                + deg_s
                + f" | {PROGRAM_CACHE.describe()}")


COMPILE_LOG = CompileLog()


def _install_monitoring() -> bool:
    """Best-effort: route jax's own compile-phase duration events into
    COMPILE_LOG (jax.monitoring exists on this jaxlib; gate anyway — the
    listener API is not a stability promise)."""
    try:
        from jax import monitoring

        def _listen(event: str, secs: float, **kw):
            # keep only the compilation pipeline events; key by tail name
            if "compil" in event or "trace" in event or "lower" in event:
                COMPILE_LOG.note_duration(event.rsplit("/", 1)[-1], secs)

        monitoring.register_event_duration_secs_listener(_listen)
        return True
    except Exception:  # noqa: BLE001 - observability must never break runs
        return False


_MONITORING = _install_monitoring()


class ProgramCache:
    """LRU cache of jitted runner callables keyed on (backend, runtime
    structural signature, runner kind).

    Eviction only drops the SHARED entry — Runtimes that already resolved
    a runner keep their reference (functools.cached_property), so an
    evicted entry costs at most one recompile for a future construction,
    never a dangling executable. Size via MADSIM_PROGRAM_CACHE_SIZE
    (entries hold compiled executables alive; the default bounds a long
    test session's RAM)."""

    def __init__(self, maxsize: int | None = None):
        if maxsize is None:
            maxsize = int(os.environ.get("MADSIM_PROGRAM_CACHE_SIZE", "128"))
        self.maxsize = max(1, maxsize)
        self._entries: collections.OrderedDict = collections.OrderedDict()
        self._lock = threading.RLock()
        self.hits = 0
        self.misses = 0
        self.unhashable = 0
        self.evictions = 0

    def get(self, key: Any, build: Callable[[], Any]) -> Any:
        """The cached value for `key`, building (and caching) on miss.
        An unhashable key — a signature ingredient froze to something
        mutable — degrades to per-call building, never to a wrong hit."""
        import jax
        full = (jax.default_backend(), key)
        try:
            hash(full)
        except TypeError:
            with self._lock:
                self.unhashable += 1
            return build()
        with self._lock:
            if full in self._entries:
                self.hits += 1
                self._entries.move_to_end(full)
                return self._entries[full]
        val = build()   # outside the lock: build may trigger work
        with self._lock:
            if full in self._entries:      # lost a race: keep the winner
                self.hits += 1
                return self._entries[full]
            self.misses += 1
            self._entries[full] = val
            while len(self._entries) > self.maxsize:
                self._entries.popitem(last=False)
                self.evictions += 1
        return val

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()

    def __len__(self) -> int:
        return len(self._entries)

    def stats(self) -> dict:
        with self._lock:
            return dict(entries=len(self._entries), hits=self.hits,
                        misses=self.misses, unhashable=self.unhashable,
                        evictions=self.evictions, maxsize=self.maxsize)

    def describe(self) -> str:
        s = self.stats()
        return (f"program cache: {s['entries']} entries, {s['hits']} hits, "
                f"{s['misses']} misses"
                + (f", {s['unhashable']} unhashable" if s['unhashable']
                   else "")
                + (f", {s['evictions']} evicted" if s['evictions'] else ""))


PROGRAM_CACHE = ProgramCache()
