"""Engine step: tokens scheduled over the ``rows x chunk bucket`` positions
the step computed, over the window's rounds, in percent."""


def read(run):
    w0, w1 = run.window_ns
    rounds = [(tok, pos) for t, tok, pos, _, _ in run.probe.rounds if w0 <= t < w1]
    pos = sum(p for _, p in rounds)
    return 100.0 * sum(t for t, _ in rounds) / pos if pos else None
