"""Scheduler: mean host milliseconds per ``ChunkedPrefillScheduler.schedule``
call in the window, from the benchmark's span around it."""


def read(run):
    w0, w1 = run.window_ns
    ms = [(e - s) / 1e6 for name, s, e in run.probe.spans
          if name == "schedule" and w0 <= s < w1]
    return sum(ms) / len(ms) if ms else None
