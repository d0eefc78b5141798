"""device_idle_share: the share of the traced window in which no operation
ran on the busiest device, in percent."""


def read(t):
    return 100.0 * (1.0 - max(t.busy_s_by_device) / t.window_s)
