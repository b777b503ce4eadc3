"""device_idle_share.<metric>: share of the traced slice in which no
operation ran on the device (1 - union of operation intervals / slice)."""


def read(ctx):
    view = ctx.trace
    if view is None or not view.devices() or view.window_s <= 0:
        return None
    return 100.0 * (1.0 - view.busy_s() / view.window_s)
