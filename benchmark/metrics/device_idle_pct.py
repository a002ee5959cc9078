"""device_idle_pct: the share of the traced window in which no operation
runs on the device, averaged over the chips used. Moves tokens_per_s."""


def read(run):
    t = run.trace
    return 100.0 * (1.0 - t.busy_s / t.window_s)
