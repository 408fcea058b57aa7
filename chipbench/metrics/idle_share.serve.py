"""Share of the traced window in which no operation ran on the device."""


def read(data):
    t = data.get("trace")
    if t is None or data.get("kind") != "serve" or t.window_s <= 0:
        return None
    return 100 * t.idle_share
