"""Collectives, trace: on the busiest device, the time in which a collective
runs and no compute does, over the traced window, in percent."""


def read(run: dict):
    t = run.get("trace")
    if not t or t.get("collective_exposed_s") is None:
        return None
    return 100.0 * t["collective_exposed_s"] / t["window_s"]
