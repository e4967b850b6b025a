"""idle_share: the share of the traced window in which no operation ran on
the device, averaged over the chips: 1 - busy / window."""


def read(run):
    return 100.0 * (1.0 - run.trace.busy_s() / run.trace.window_s())
