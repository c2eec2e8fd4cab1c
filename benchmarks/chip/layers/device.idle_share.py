"""Layer device: 1 - (union of device-op intervals) / traced window,
averaged over the chips."""


def read(window):
    return None if window.trace is None else window.trace.idle_share
