"""Device: 100 x (1 - union of device-op intervals / traced window)."""


def read(view):
    d = view["device"]
    if not d or d["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - d["busy_s"] / d["window_s"])
