"""Device: 1 - (union of op intervals on the device) / traced window."""

from chipbench.metrics._device import idle_share


def read(run):
    return idle_share(run)
