"""Share of the traced window in which no operation ran on the device,
averaged over the cell's chips: 100 * (1 - busy / window)."""


def read(view):
    s = view["summary"]
    if s["window_s"] <= 0 or not s["chips"]:
        return None
    return 100.0 * (1.0 - s["busy_s"] / s["window_s"])
