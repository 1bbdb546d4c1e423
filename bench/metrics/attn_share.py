"""Kernels: device seconds of the leaf ops under the program's ``attention``
scope over those of all leaf ops in the traced window, in percent, from
``run.trace["device_s_by_scope"]`` (``bench.scopes.device_s_by_scope``).
A trace without that table, or of a program without device scopes, reads
nothing."""


def read(run):
    by_scope = (run.trace or {}).get("device_s_by_scope") or {}
    total = sum(by_scope.values())
    if total <= 0 or set(by_scope) <= {"other"}:
        return None
    return 100.0 * by_scope.get("attention", 0.0) / total
