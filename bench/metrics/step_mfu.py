"""Engine step: model FLOPs of the tokens scheduled in the traced window
(``bench.flops``) over the device's busy time there times the bf16 peak of
its ``device_kind``, in percent."""


def read(run):
    if run.trace is None or run.trace["busy_s"] <= 0:
        return None
    a, b = run.trace_window_ns
    f = sum(f for t, _, _, f, _ in run.probe.rounds if a <= t < b)
    return 100.0 * f / (run.trace["busy_s"] * run.peak["bf16_flops_per_s"])
