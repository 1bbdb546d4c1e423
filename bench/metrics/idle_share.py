"""Device: share of the traced time during which a replica had work
outstanding and no operation ran on its device, in percent.  Over several
devices (a fleet, one replica a chip) each device counts only the time its
own replica had work, and the metric is the highest device's share."""


def read(run):
    if run.trace is None:
        return None
    devices = run.trace.get("per_device") or {"all": run.trace}
    shares = [100.0 * v["idle_work_s"] / v["work_s"]
              for v in devices.values() if v["work_s"] > 0]
    return max(shares) if shares else None
