"""`device_idle_share.serve`: the device's idle share of the traced window, in per cent
(1 - union of device-operation intervals / window)."""
from bench.metrics._device import idle_share


def read(r: dict):
    return idle_share(r)
