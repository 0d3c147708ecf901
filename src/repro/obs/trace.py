"""TraceRecorder: per-step, per-message event timelines of the executed
compression pipeline.

The paper's complaint is that theory reasons about an idealized pipeline
while implementations run a different one; our own `simulate_schedule` is
exactly such a model. The TraceRecorder records what a step ACTUALLY did:
one span per pipeline stage (compress, pack, decode, collective,
ef_update) per wire message — or one span per message on the unpacked
path, and one per size-class dispatch on the bare-plan path — with
bucket/message/codec attribution, exported as Chrome trace-event JSON
(load in Perfetto / chrome://tracing) plus a compact per-step summary.

Mechanics. Instrumented execution hooks (core.plan / core.schedule /
core.wire accept a duck-typed ``recorder=``; core never imports obs) do
two things at jit-trace time:

  * wrap each stage in ``jax.named_scope`` so XLA profiles carry the
    same ``repro/msg…`` names, and
  * insert a ``jax.debug.callback`` whose operand DATA-DEPENDS on the
    stage's outputs, stamping the host clock when execution reaches the
    end of the stage (per executed step, in execution order).

A span's duration is the gap between consecutive stamps in timestamp
order — an honest host-side view of the serialized CPU stream, not a
device profile (XLA may overlap work; the barrier chain between messages
only pins program order). Trust span STRUCTURE and counts anywhere;
trust durations only for relative comparisons on a quiet machine.

Zero-overhead contract: every hook guards on ``recorder is not None and
recorder.enabled``, so with recording disabled the traced graph is
bit-identical to the uninstrumented one (no callbacks, no scopes, no
extra ops — tests/test_obs.py compares jaxprs).
"""
from __future__ import annotations

import contextlib
import functools
import json
import time
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp

__all__ = ["TraceRecorder", "active", "validate_chrome_trace",
           "format_step_summary", "count_debug_callbacks"]

#: bump when the exported chrome-trace "args" layout changes
TRACE_SCHEMA_VERSION = 1

_ALLOWED_PH = {"X", "i", "M"}


def active(recorder) -> Optional["TraceRecorder"]:
    """The one-line guard every instrumented hook runs: the recorder if
    it exists and is enabled, else None (→ the uninstrumented graph).
    Duck-typed so core modules can inline the same check without
    importing obs."""
    if recorder is not None and getattr(recorder, "enabled", False):
        return recorder
    return None


def _dep_token(dep):
    """Collapse the stage outputs a mark depends on into one f32 scalar
    — the debug-callback operand. Cheap (one element per array) and
    un-hoistable: the callback cannot fire before every listed array is
    computed."""
    arrays = dep if isinstance(dep, (list, tuple)) else [dep]
    toks = [jnp.ravel(a)[0].astype(jnp.float32) for a in arrays]
    tok = toks[0]
    for t in toks[1:]:
        tok = tok + t
    return tok


class TraceRecorder:
    """Records stage marks from instrumented execution into Chrome
    trace events. One recorder serves many traced functions and many
    steps; call :meth:`finalize_step` after each blocked-on step to
    convert that step's marks into spans."""

    def __init__(self, enabled: bool = True, pid: int = 0,
                 clock=time.perf_counter_ns):
        self.enabled = bool(enabled)
        self.pid = pid
        self._clock = clock
        self.events: List[Dict] = []      # finalized chrome events
        self.steps: List[Dict] = []       # per-step summaries
        self._meta: List[Dict] = []       # static span metadata (trace time)
        self._marks: List = []            # (meta_id, t_ns) runtime stamps
        self._t0: Optional[int] = None    # trace epoch (first stamp)

    # ---- trace-time hooks (called while jit is tracing) ------------------
    def scope(self, name: str):
        """named_scope wrapper so XLA profiles carry the span names."""
        return jax.named_scope(name)

    def begin(self, dep, label: str = "inputs_ready") -> None:
        """Stamp the moment the instrumented region's INPUTS are
        computed — the baseline the first span's duration is measured
        from (otherwise it would swallow backward time)."""
        self._mark(dep, "begin", cat="begin", label=label)

    def mark(self, dep, stage: str, *, cat: str = "stage",
             message: Optional[int] = None,
             bucket_ids: Optional[Sequence[int]] = None,
             dims: Optional[Sequence[int]] = None,
             n_units: Optional[int] = None,
             codec: Optional[str] = None,
             label: Optional[str] = None,
             nbytes: Optional[int] = None) -> None:
        """Register one pipeline-stage end: static attribution now, a
        host-clock stamp (data-dependent on `dep`) per executed step.
        `nbytes`: the bytes the stage sends (a ring hop's)."""
        self._mark(dep, stage, cat=cat, message=message,
                   bucket_ids=bucket_ids, dims=dims, n_units=n_units,
                   codec=codec, label=label, nbytes=nbytes)

    def _mark(self, dep, stage: str, **meta) -> None:
        mid = len(self._meta)
        m = {"stage": stage}
        m.update({k: v for k, v in meta.items() if v is not None})
        if "bucket_ids" in m:
            m["bucket_ids"] = tuple(int(b) for b in m["bucket_ids"])
        if "dims" in m:
            m["dims"] = tuple(int(d) for d in m["dims"])
        self._meta.append(m)
        jax.debug.callback(functools.partial(self._stamp, mid),
                           _dep_token(dep))

    def _stamp(self, mid: int, _tok) -> None:
        self._marks.append((mid, self._clock()))

    # ---- host-side spans -------------------------------------------------
    @contextlib.contextmanager
    def host_span(self, name: str, cat: str = "host", **args):
        """Wall-clock span around a host-side region (e.g. a blocked-on
        prefill, a compile). Also enters jax.profiler.TraceAnnotation so
        an XLA profile taken concurrently carries the same name."""
        if not self.enabled:
            yield
            return
        t0 = self._clock()
        with jax.profiler.TraceAnnotation(name):
            yield
        t1 = self._clock()
        if self._t0 is None:
            self._t0 = t0
        self.events.append({
            "name": name, "cat": cat, "ph": "X",
            "ts": round((t0 - self._t0) / 1e3, 3),
            "dur": round((t1 - t0) / 1e3, 3),
            "pid": self.pid, "tid": 0,
            "args": dict(args),
        })

    # ---- finalization ----------------------------------------------------
    def finalize_step(self, step: Optional[int] = None, *,
                      dedupe: bool = False) -> Dict:
        """Convert the marks stamped since the last finalize into spans.
        Call after the step's outputs are blocked on (all callbacks for
        the step have then fired). Returns the per-step summary.

        ``dedupe=True`` collapses repeated stamps of the SAME mark to the
        latest one. Under a multi-device ``shard_map`` each debug
        callback fires once per local device, so every mark stamps
        n_devices times; keeping the last arrival per mark restores the
        one-stamp-per-stage timeline (span end = the moment the slowest
        device finished the stage). Required whenever the traced fn ran
        on >1 local device; a no-op on single-device runs."""
        if dedupe:
            latest: Dict[int, int] = {}
            for mid, t_ns in self._marks:
                if mid not in latest or t_ns > latest[mid]:
                    latest[mid] = t_ns
            self._marks = list(latest.items())
        marks = sorted(self._marks, key=lambda m: m[1])
        self._marks = []
        step = len(self.steps) if step is None else int(step)
        if not marks:
            summary = {"step": step, "n_spans": 0, "n_message_spans": 0,
                       "stage_us": {}, "wall_us": 0.0}
            self.steps.append(summary)
            return summary
        if self._t0 is None:
            self._t0 = marks[0][1]
        spans = []
        prev_ns = None
        for mid, t_ns in marks:
            meta = self._meta[mid]
            if meta["stage"] == "begin":
                prev_ns = t_ns
                continue
            start = prev_ns if prev_ns is not None else t_ns
            spans.append((start, t_ns, meta))
            prev_ns = t_ns
        # direct span events
        msg_seen = set()
        by_msg: Dict[int, List] = {}
        stage_us: Dict[str, float] = {}
        for start, end, meta in spans:
            dur = (end - start) / 1e3
            cat = meta.get("cat", "stage")
            mi = meta.get("message")
            name = meta.get("label") or (
                f"{meta['stage']} m{mi}" if mi is not None
                else meta["stage"])
            args = {"step": step, "stage": meta["stage"],
                    "schema_version": TRACE_SCHEMA_VERSION}
            for k in ("message", "bucket_ids", "dims", "n_units", "codec",
                      "nbytes"):
                if k in meta:
                    args[k] = (list(meta[k])
                               if isinstance(meta[k], tuple) else meta[k])
            self.events.append({
                "name": name, "cat": cat, "ph": "X",
                "ts": round((start - self._t0) / 1e3, 3),
                "dur": round(dur, 3),
                "pid": self.pid, "tid": 0, "args": args,
            })
            stage_us[meta["stage"]] = stage_us.get(meta["stage"], 0.0) + dur
            if mi is not None:
                if cat == "message":
                    msg_seen.add(mi)
                else:
                    by_msg.setdefault(mi, []).append((start, end, meta))
        # synthesize a cat="message" umbrella span per message that only
        # emitted stage spans (the wire path), so span-count == n_messages
        # holds on every instrumented path
        n_message_spans = len(msg_seen)
        for mi in sorted(k for k in by_msg if k not in msg_seen):
            group = by_msg[mi]
            start = min(s for s, _, _ in group)
            end = max(e for _, e, _ in group)
            meta0 = group[0][2]
            args = {"step": step, "stage": "message",
                    "schema_version": TRACE_SCHEMA_VERSION, "message": mi,
                    "stages": sorted({m["stage"] for _, _, m in group})}
            for k in ("bucket_ids", "dims", "n_units", "codec"):
                if k in meta0:
                    args[k] = (list(meta0[k])
                               if isinstance(meta0[k], tuple) else meta0[k])
            self.events.append({
                "name": f"message m{mi}", "cat": "message", "ph": "X",
                "ts": round((start - self._t0) / 1e3, 3),
                "dur": round((end - start) / 1e3, 3),
                "pid": self.pid, "tid": 1, "args": args,
            })
            n_message_spans += 1
        summary = {
            "step": step,
            "n_spans": len(spans),
            "n_message_spans": n_message_spans,
            "stage_us": {k: round(v, 3) for k, v in sorted(stage_us.items())},
            "wall_us": round((marks[-1][1] - marks[0][1]) / 1e3, 3),
        }
        self.steps.append(summary)
        return summary

    # ---- queries ---------------------------------------------------------
    def span_events(self, cat: Optional[str] = None,
                    step: Optional[int] = None) -> List[Dict]:
        out = []
        for e in self.events:
            if e.get("ph") != "X":
                continue
            if cat is not None and e.get("cat") != cat:
                continue
            if step is not None and e.get("args", {}).get("step") != step:
                continue
            out.append(e)
        return out

    def message_spans(self, step: Optional[int] = None) -> List[Dict]:
        """The per-message spans of one step (or all steps) — the
        acceptance-gate count: len == schedule.num_messages per step."""
        return self.span_events(cat="message", step=step)

    # ---- export ----------------------------------------------------------
    def chrome_trace(self) -> Dict:
        """The Chrome trace-event JSON object (Perfetto-loadable)."""
        meta_events = [{
            "name": "process_name", "ph": "M", "pid": self.pid, "tid": 0,
            "args": {"name": "repro"},
        }]
        return {
            "traceEvents": meta_events + self.events,
            "displayTimeUnit": "ms",
            "metadata": {"schema_version": TRACE_SCHEMA_VERSION,
                         "tool": "repro.obs.trace",
                         "steps": self.steps},
        }

    def export(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f, indent=2, sort_keys=True)


def validate_chrome_trace(obj: Any) -> bool:
    """Validate an object against the Chrome trace-event schema subset
    this module emits (dict with a traceEvents list of M/i/X events;
    every X event carries numeric non-negative ts/dur and a name).
    Raises ValueError on the first violation; returns True when valid."""
    if not isinstance(obj, dict):
        raise ValueError(f"trace must be a dict, got {type(obj).__name__}")
    events = obj.get("traceEvents")
    if not isinstance(events, list):
        raise ValueError("trace['traceEvents'] must be a list")
    for i, e in enumerate(events):
        if not isinstance(e, dict):
            raise ValueError(f"traceEvents[{i}] is not a dict")
        ph = e.get("ph")
        if ph not in _ALLOWED_PH:
            raise ValueError(f"traceEvents[{i}]: bad ph {ph!r}")
        if not isinstance(e.get("name"), str):
            raise ValueError(f"traceEvents[{i}]: name must be a string")
        if not isinstance(e.get("pid"), int) or not isinstance(
                e.get("tid"), int):
            raise ValueError(f"traceEvents[{i}]: pid/tid must be ints")
        if ph in ("X", "i"):
            ts = e.get("ts")
            if not isinstance(ts, (int, float)) or ts < 0:
                raise ValueError(f"traceEvents[{i}]: bad ts {ts!r}")
        if ph == "X":
            dur = e.get("dur")
            if not isinstance(dur, (int, float)) or dur < 0:
                raise ValueError(f"traceEvents[{i}]: bad dur {dur!r}")
        if "args" in e and not isinstance(e["args"], dict):
            raise ValueError(f"traceEvents[{i}]: args must be a dict")
    return True


def format_step_summary(summary: Dict) -> str:
    """One human line per step — what quickstart/train print."""
    stages = ", ".join(f"{k} {v:.0f}us"
                       for k, v in summary["stage_us"].items())
    return (f"step {summary['step']}: {summary['n_message_spans']} message "
            f"spans, {summary['n_spans']} stage spans, "
            f"{summary['wall_us']:.0f}us wall ({stages})")


def count_debug_callbacks(fn, *args) -> int:
    """How many debug_callback equations one jit trace of fn(*args)
    stages — the zero-overhead gate's counter (the obs twin of
    kernels.ops.count_pallas_calls): 0 with recording disabled."""
    jaxpr = jax.make_jaxpr(fn)(*args)

    def walk(jx) -> int:
        n = 0
        for eqn in jx.eqns:
            if "debug_callback" in eqn.primitive.name:
                n += 1
            for v in eqn.params.values():
                if hasattr(v, "jaxpr"):
                    n += walk(v.jaxpr)
                elif isinstance(v, (list, tuple)):
                    for u in v:
                        if hasattr(u, "jaxpr"):
                            n += walk(u.jaxpr)
        return n

    return walk(jaxpr.jaxpr)
