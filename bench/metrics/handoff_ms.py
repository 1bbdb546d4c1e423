"""Router: median milliseconds from a handed-off request's first token,
delivered by its prefill replica, to its second, delivered by the decode
replica that restored its KV: the gap that the handoff and the restore put
into the request's stream.  Over the requests whose first token falls in the
window, from the per-request token stamps (``run.requests``).  A run with
no handed-off request there reads nothing."""
from statistics import median


def read(run):
    w0, w1 = run.window_ns
    gaps = [(r.token_ns[1] - r.token_ns[0]) / 1e6 for r in run.requests
            if r.handoffs and len(r.token_ns) >= 2 and w0 <= r.token_ns[0] < w1]
    return median(gaps) if gaps else None
