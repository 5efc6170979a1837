"""Device: the share of the traced window in which no operation ran on the
device (1 - union of the device's operations over the window), averaged
over the chips used, in %."""


def read(ctx):
    return 100.0 * ctx["trace"].idle_share
