"""Device, trace: 1 - (union of device-op intervals over the traced window),
on the device that was busy longest, in percent."""


def read(run: dict):
    t = run.get("trace")
    if not t or not t["per_device_busy_s"]:
        return None
    return 100.0 * (1.0 - max(t["per_device_busy_s"]) / t["window_s"])
