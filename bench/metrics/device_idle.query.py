"""Share of the traced window in which no operation ran on the device."""


def read(ctx):
    red = ctx["trace"]
    if ctx["kind"] != "query" or not red or not red["devices"]:
        return None
    return (1.0 - red["busy_s"] / red["window_s"]) * 100.0
