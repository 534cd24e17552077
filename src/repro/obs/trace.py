"""Host-side spans — one clock discipline for every wall-clock number.

`span("serve_step", exec_path=...)` measures host wall time with
`time.perf_counter` (monotonic — never `time.time`, which steps under NTP),
optionally blocking on a jax value at close so the measurement covers device
execution, and emits a `jax.profiler.TraceAnnotation` so device traces line
up with host spans when a `--profile-dir` window is open. Spans nest (each
records its parent) and carry the current correlation ids from
:mod:`repro.obs.events`, so they join against sensor rows and journal
decisions.

Disabled (the default), `span()` returns ONE shared no-op context manager and
records nothing — the acceptance bar is < 3 % serve-step overhead with
observability off, so the disabled path is a dict lookup and a constant
return, no allocation.
"""

from __future__ import annotations

import time
from typing import Any

now = time.perf_counter  # THE clock for wall-time measurements, repo-wide

_STATE: dict[str, Any] = {
    "enabled": False,
    "spans": [],          # completed SpanRecord dicts, append order = close order
    "stack": [],          # open span ids (nesting)
    "next_id": 1,
    "max_spans": 262_144,  # hard cap: a runaway loop must not OOM the host
    "dropped": 0,
}


def enable(*, max_spans: int | None = None) -> None:
    _STATE["enabled"] = True
    if max_spans is not None:
        _STATE["max_spans"] = int(max_spans)


def disable() -> None:
    _STATE["enabled"] = False


def is_enabled() -> bool:
    return _STATE["enabled"]


def spans() -> list[dict[str, Any]]:
    """Completed spans so far (the live buffer — do not mutate)."""
    return _STATE["spans"]


def drain_spans() -> list[dict[str, Any]]:
    """Return and clear the completed-span buffer."""
    out, _STATE["spans"] = _STATE["spans"], []
    _STATE["dropped"] = 0
    return out


class _NoopSpan:
    """Shared do-nothing span for the disabled path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def sync(self, value):
        return value

    def tag(self, **tags):
        return self


_NOOP = _NoopSpan()


class _Span:
    __slots__ = ("name", "tags", "span_id", "parent_id", "_t0", "_sync",
                 "_annotation")

    def __init__(self, name: str, tags: dict[str, Any]):
        self.name = name
        self.tags = tags
        self.span_id = 0
        self.parent_id = 0
        self._t0 = 0.0
        self._sync = None
        self._annotation = None

    def sync(self, value):
        """Register a jax value to block_until_ready at span close, so the
        span covers device execution, not just dispatch. Returns the value."""
        self._sync = value
        return value

    def tag(self, **tags):
        """Attach tags discovered inside the span (e.g. tokens emitted)."""
        self.tags.update(tags)
        return self

    def __enter__(self):
        state = _STATE
        self.span_id = state["next_id"]
        state["next_id"] += 1
        stack = state["stack"]
        self.parent_id = stack[-1] if stack else 0
        stack.append(self.span_id)
        try:
            import jax

            self._annotation = jax.profiler.TraceAnnotation(self.name)
            self._annotation.__enter__()
        except Exception:  # profiler backends may be absent headless
            self._annotation = None
        self._t0 = now()
        return self

    def __exit__(self, *exc):
        if self._sync is not None:
            import jax

            jax.block_until_ready(self._sync)
        dur = now() - self._t0
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        state = _STATE
        stack = state["stack"]
        if stack and stack[-1] == self.span_id:
            stack.pop()
        if len(state["spans"]) < state["max_spans"]:
            from repro.obs.events import current_ids

            record = {
                "name": self.name,
                "span_id": self.span_id,
                "parent_id": self.parent_id,
                "dur_s": dur,
                **self.tags,
            }
            ids = current_ids()
            if ids:
                record["trace"] = ids
            state["spans"].append(record)
        else:
            state["dropped"] += 1
        return False


def span(name: str, **tags: Any):
    """Open a measurement span. Usage:

        with span("serve_step", exec_path="compact") as sp:
            out = decode(...)
            sp.sync(out)        # block_until_ready at close

    Disabled → the shared no-op (no allocation, no record)."""
    if not _STATE["enabled"]:
        return _NOOP
    return _Span(name, tags)


# ------------------------------------------------------ device-trace windows

_PROFILE: dict[str, Any] = {"dir": None}


def start_profile(log_dir: str) -> None:
    """Open a `jax.profiler.trace` window writing to `log_dir`. Host spans
    emitted inside the window line up with the device trace through their
    TraceAnnotations. A profiler that cannot start raises: a run that asked
    for a trace must not finish without one."""
    import jax

    jax.profiler.start_trace(log_dir)
    _PROFILE["dir"] = log_dir


def stop_profile() -> str:
    """Close the open profiler window and return its directory. Raises when
    no window is open or the profiler fails to write the trace."""
    log_dir, _PROFILE["dir"] = _PROFILE["dir"], None
    if log_dir is None:
        raise RuntimeError("stop_profile: no profiler window is open")
    import jax

    jax.profiler.stop_trace()
    return log_dir


def write_spans_jsonl(path: str, *, drain: bool = True) -> int:
    """Append the span buffer to a JSONL file (one span per row). Returns the
    number of rows written; with `drain` (default) the buffer is cleared."""
    import json

    rows = drain_spans() if drain else list(spans())
    if not rows:
        return 0
    with open(path, "a") as f:
        for row in rows:
            f.write(json.dumps(row) + "\n")
    return len(rows)
