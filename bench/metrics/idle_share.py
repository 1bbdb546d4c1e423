"""Device: share of the traced time during which the replica had work
outstanding and no operation ran on the device, in percent."""


def read(run):
    if run.trace is None or run.trace["work_s"] <= 0:
        return None
    return 100.0 * run.trace["idle_work_s"] / run.trace["work_s"]
