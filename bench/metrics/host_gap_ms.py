"""Engine loop (host): mean host milliseconds per round from the end of a
round's ``drain.wait`` (its tokens are host-visible) to the end of the next
``launch`` (the next step is queued on the device), from the program's own
spans (``run.program``: its ``repro.engine.trace.Recorder``), over the
window's rounds after the profiler stopped.  A run without the program's
spans reads nothing."""
from bisect import bisect_left


def read(run):
    program = getattr(run, "program", None)
    if program is None:
        return None
    a, b = max(run.trace_window_ns[1], run.window_ns[0]), run.window_ns[1]
    launches = sorted(e for n, _, e, _, _ in program.spans if n == "launch")
    gaps = []
    for n, _, ready, _, _ in program.spans:
        if n != "drain.wait" or not a <= ready < b:
            continue
        i = bisect_left(launches, ready)
        if i < len(launches) and launches[i] < b:
            gaps.append((launches[i] - ready) / 1e6)
    return sum(gaps) / len(gaps) if gaps else None
