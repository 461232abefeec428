"""Share (%) of the traced training window in which no operation ran on
the device: 1 - (union of device-op intervals) / window, averaged over the
chips used."""

from chipbench import trace


def read(ctx):
    return trace.idle_share(ctx["trace"])
