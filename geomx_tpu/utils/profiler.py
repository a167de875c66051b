"""Tracing/profiling: Chrome-trace event recording + XLA device traces.

Parity with the reference's profiler subsystem (src/profiler/profiler.h:256,
aggregate_stats.cc): named scopes are recorded as Chrome trace events and
dumped to a ``chrome://tracing``-loadable JSON file; ``aggregate_stats()``
reproduces the reference's per-name aggregate table (count/total/min/max/avg).
Device-side profiling delegates to ``jax.profiler`` (start_trace/stop_trace
TensorBoard traces and per-op annotations via TraceAnnotation), the TPU
analogue of the reference's engine-thread operator profiling.

The reference can also drive profilers on *remote PS servers* from a worker
via kvstore commands (kSetProfilerParams, src/kvstore/kvstore_dist.h:197-203;
server side src/kvstore/kvstore_dist_server.h:383-430, filename prefixed
with the server's rank at :415).  `GeoPSServer` exposes the same surface:
COMMAND {cmd: "set_profiler_params"|"profiler_start"|"profiler_stop"|
"profiler_dump"}, with the dump path prefixed ``rank<k>_``.
"""

from __future__ import annotations

import contextlib
import os
import threading
import time
from typing import Any, Dict, List, Optional

# the two JAX halves of a scope, resolved once at import: a name-stack
# entry that tags every op traced inside it, and a host span on the
# profiler's own clock (the one the device planes share)
from jax import named_scope as _named_scope
from jax.profiler import TraceAnnotation as _TraceAnnotation


class Profiler:
    """Host-side Chrome-trace profiler with optional device trace capture.

    Modes mirror the reference's MXSetProcessProfilerConfig /
    MXDumpProcessProfile cycle: configure -> set_state(run) ->
    scopes/events accumulate -> dump.
    """

    def __init__(self, filename: str = "profile.json",
                 profile_all: bool = True,
                 rank: Optional[int] = None,
                 max_events: int = 1_000_000):
        self.filename = filename
        self.profile_all = profile_all
        self.rank = rank
        self.running = False
        # bounded buffer: a profiler left running for a long job must
        # not grow without limit — past max_events new events are
        # DROPPED and counted, and the dump metadata reports both
        # (num_events / dropped_events) so a truncated trace is
        # self-describing instead of silently partial
        self.max_events = int(max_events)
        self._dropped = 0
        self._events: List[Dict[str, Any]] = []
        self._lock = threading.Lock()
        self._t0 = time.perf_counter()
        # wall-clock anchor of the trace's t=0: merge_traces
        # (telemetry/tracing.py) aligns per-process monotonic clocks on
        # it, so N parties' dumps land on one real timeline
        self._anchor_unix_us = time.time() * 1e6
        self._device_trace_dir: Optional[str] = None
        # stable registry-assigned trace lane per thread:
        # threading.get_ident() % 100000 could alias two threads into one
        # lane, so the first event from a thread claims the next small id
        # and the thread's name becomes lane metadata at dump time
        self._tid_ids: Dict[int, int] = {}
        self._tid_names: Dict[int, str] = {}

    # ---- configuration (reference kSetProfilerParams payload) -------------
    def set_config(self, filename: Optional[str] = None,
                   profile_all: Optional[bool] = None,
                   **_ignored) -> None:
        if filename is not None:
            self.filename = filename
        if profile_all is not None:
            self.profile_all = bool(profile_all)

    def set_state(self, run: bool) -> None:
        self.running = bool(run)

    # ---- event recording ---------------------------------------------------
    def _now_us(self) -> float:
        return (time.perf_counter() - self._t0) * 1e6

    def now_us(self) -> float:
        """The trace clock (microseconds since profiler construction) —
        the same timebase event ``ts`` values carry, so a caller can
        mark a window boundary and later attribute only spans recorded
        after it (``attribute_trace(..., since_us=...)``)."""
        return self._now_us()

    def _tid_locked(self) -> int:
        """Stable small trace-lane id for the calling thread (caller
        holds self._lock)."""
        ident = threading.get_ident()
        tid = self._tid_ids.get(ident)
        if tid is None:
            tid = self._tid_ids[ident] = len(self._tid_ids)
            self._tid_names[tid] = threading.current_thread().name
        return tid

    def _append_locked(self, ev: Dict[str, Any]) -> None:
        """Record one event under the buffer cap (caller holds
        self._lock): past max_events the event is dropped and counted."""
        if len(self._events) >= self.max_events:
            self._dropped += 1
            return
        self._events.append(ev)

    def add_event(self, name: str, begin_us: float, end_us: float,
                  category: str = "host", args: Optional[Dict] = None):
        if not self.running:
            return
        with self._lock:
            self._append_locked({
                "name": name, "cat": category, "ph": "X",
                "ts": begin_us, "dur": end_us - begin_us,
                "pid": os.getpid(), "tid": self._tid_locked(),
                "args": args or {},
            })

    def instant(self, name: str, category: str = "host",
                args: Optional[Dict] = None):
        if not self.running:
            return
        with self._lock:
            ev = {
                "name": name, "cat": category, "ph": "i", "s": "g",
                "ts": self._now_us(), "pid": os.getpid(),
                "tid": self._tid_locked(),
            }
            if args:
                ev["args"] = dict(args)
            self._append_locked(ev)

    def counter(self, name: str, values: Dict[str, float],
                category: str = "host"):
        """Chrome-trace counter sample (ph "C"): a named value track.
        The pipelined sync engine (sync/pipeline.py) samples
        ``<axis>_pipeline_inflight`` {bytes} here so the trace shows the
        WAN payload parked between its launch span and the next step's
        apply span."""
        if not self.running:
            return
        with self._lock:
            self._append_locked({
                "name": name, "cat": category, "ph": "C",
                "ts": self._now_us(), "pid": os.getpid(),
                "args": dict(values),
            })

    @contextlib.contextmanager
    def scope(self, name: str, category: str = "host",
              args: Optional[Dict] = None):
        """The program's one span primitive; one call feeds three sinks.

        Always: ``jax.named_scope(name)``: inside a ``jit`` trace every
        op traced under it carries the name in its ``op_name`` metadata
        (``tf_op`` in a profile, ``metadata={op_name=...}`` in the
        compiled HLO; ``telemetry/layers.py`` reads the latter), outside
        it is a name-stack push; and ``jax.profiler.TraceAnnotation(name,
        **args)``: a host span in the profiler's own trace while a
        ``jax.profiler`` session runs, near-free otherwise.  Only while
        this profiler is ``running``: the Chrome-trace event, with
        ``args`` as its structured metadata (the bucketed communication
        engine reports per-bucket payload sizes there).

        Inside jitted code the host span and the Chrome event measure the
        *tracing* of the enclosed code, once per compile, not its run on
        the device; the device time is found through the op names."""
        with _named_scope(name), _TraceAnnotation(name, **(args or {})):
            if not self.running:
                yield
                return
            begin = self._now_us()
            try:
                yield
            finally:
                self.add_event(name, begin, self._now_us(), category, args)

    # ---- device (XLA) traces ----------------------------------------------
    def start_device_trace(self, logdir: str) -> None:
        import jax.profiler as jp
        self._device_trace_dir = logdir
        jp.start_trace(logdir)

    def stop_device_trace(self) -> None:
        if self._device_trace_dir is None:
            return
        import jax.profiler as jp
        jp.stop_trace()
        self._device_trace_dir = None

    # ---- output ------------------------------------------------------------
    def _dump_path(self) -> str:
        # reference prefixes the dump filename with the server's rank
        # (kvstore_dist_server.h:415)
        if self.rank is None:
            return self.filename
        d, b = os.path.split(self.filename)
        return os.path.join(d, f"rank{self.rank}_{b}")

    def to_doc(self) -> Dict[str, Any]:
        """The trace as a Chrome document (what ``dump`` serializes):
        events plus lane-name metadata rows, with self-describing
        accounting in ``metadata`` — ``num_events``/``num_spans`` this
        trace holds and ``dropped_events`` the buffer cap discarded, so
        a truncated trace announces its truncation instead of reading
        as a complete record (the in-process consumer is the step-time
        attribution layer, telemetry/attribution.py)."""
        with self._lock:
            events = list(self._events)
            names = dict(self._tid_names)
            dropped = self._dropped
        pid = os.getpid()
        num_spans = sum(1 for e in events if e.get("ph") == "X")
        for tid, tname in sorted(names.items()):
            events.append({"name": "thread_name", "ph": "M", "pid": pid,
                           "tid": tid, "args": {"name": tname}})
        return {"traceEvents": events, "displayTimeUnit": "ms",
                "metadata": {"anchor_unix_us": self._anchor_unix_us,
                             "rank": self.rank,
                             "num_events": len(events),
                             "num_spans": num_spans,
                             "dropped_events": dropped}}

    def dump(self, path: Optional[str] = None) -> str:
        """Write the Chrome trace ATOMICALLY: serialize to a temp file in
        the destination directory and ``os.replace`` it into place, so a
        crash (or a concurrent reader) mid-dump can never observe a
        truncated, unloadable trace.  Thread-name metadata rows label
        each registry-assigned lane; ``metadata.anchor_unix_us`` is the
        wall-clock anchor ``merge_traces`` aligns cross-party dumps on;
        ``metadata.num_events``/``num_spans``/``dropped_events`` record
        the trace's own span accounting (``to_doc``)."""
        path = path or self._dump_path()
        from geomx_tpu.utils.atomicio import atomic_json_dump
        return atomic_json_dump(path, self.to_doc())

    def aggregate_stats(self) -> Dict[str, Dict[str, float]]:
        """Per-name {count,total_us,min_us,max_us,avg_us} — the reference's
        AggregateStats table (src/profiler/aggregate_stats.cc)."""
        out: Dict[str, Dict[str, float]] = {}
        with self._lock:
            for e in self._events:
                if e.get("ph") != "X":
                    continue
                s = out.setdefault(e["name"], {
                    "count": 0, "total_us": 0.0,
                    "min_us": float("inf"), "max_us": 0.0})
                s["count"] += 1
                s["total_us"] += e["dur"]
                s["min_us"] = min(s["min_us"], e["dur"])
                s["max_us"] = max(s["max_us"], e["dur"])
        for s in out.values():
            s["avg_us"] = s["total_us"] / max(s["count"], 1)
        return out

    def reset(self) -> None:
        with self._lock:
            self._events.clear()
            self._dropped = 0


# Process-global profiler, like the reference's Profiler::Get() singleton.
_global: Optional[Profiler] = None
_global_lock = threading.Lock()


def get_profiler() -> Profiler:
    global _global
    with _global_lock:
        if _global is None:
            _global = Profiler()
        return _global


@contextlib.contextmanager
def profile_scope(name: str, category: str = "host",
                  args: Optional[Dict] = None):
    with get_profiler().scope(name, category, args=args):
        yield
