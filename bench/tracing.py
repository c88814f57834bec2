"""The profiler around a measured window (``--trace 1``).

The trace is written to a temporary directory and deleted once reduced.
The program's ``annotate=True`` spans enter the profiler trace through its
tracer's ``jax_annotations`` switch, and the window itself is one
``bench.window`` annotation, whose bounds are the traced window.  Python
function tracing stays off: it would slow the host loop that is measured.
"""
from __future__ import annotations

import contextlib
import shutil
import tempfile
from typing import Optional

from bench.yardstick import trace_reduce

WINDOW = "bench.window"


class WindowTrace:
    def __init__(self, enabled: bool, keep_dir: Optional[str] = None):
        self.enabled = enabled
        self.keep_dir = keep_dir
        self.trace: Optional[trace_reduce.Trace] = None
        self.lo = self.hi = 0
        self._dir: Optional[str] = None

    @contextlib.contextmanager
    def window(self):
        """Profile the body when enabled; always mark it as the window."""
        import jax

        if not self.enabled:
            yield
            return
        from repro.obs.trace import TRACER

        self._dir = self.keep_dir or tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self._dir, profiler_options=opts)
        TRACER.jax_annotations = True
        try:
            with jax.profiler.TraceAnnotation(WINDOW):
                yield
        finally:
            TRACER.jax_annotations = False
            jax.profiler.stop_trace()

    def reduce(self) -> None:
        """Read the trace back and find the window in it; frees the files."""
        if not self.enabled:
            return
        try:
            self.trace = trace_reduce.load(trace_reduce.find_xplane(self._dir))
        finally:
            if self.keep_dir is None:
                shutil.rmtree(self._dir, ignore_errors=True)
        marks = [e for e in self.trace.host if e.name == WINDOW]
        if not marks:
            raise RuntimeError("the traced window's annotation is not in the trace")
        self.lo, self.hi = marks[0].start, marks[0].end

    def device_fields(self) -> dict:
        return {"busy_s": trace_reduce.busy_seconds(self.trace, self.lo, self.hi),
                "window_s": (self.hi - self.lo) / 1e9}

    def breakdown(self) -> dict:
        """The longest device operations, and the device's idle time by the
        host span it fell in; where no span but the window's covers it, the
        host was in its own loop (waiting, batching, scheduling)."""
        gaps = trace_reduce.idle_gaps(self.trace, self.lo, self.hi)
        return {"device_ops": [[k, v] for k, v in trace_reduce.top_ops(self.trace, self.lo, self.hi)],
                "idle_gaps": [["host loop, no program span" if k == WINDOW else k, v]
                              for k, v in gaps]}
