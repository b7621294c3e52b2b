"""serve.device_idle: the share of the traced window in which no operation
ran on the device: 1 - (union of device op intervals) / window."""
from bench.lib.xtrace import idle_percent


def read(run: dict):
    return idle_percent(run["trace"])
