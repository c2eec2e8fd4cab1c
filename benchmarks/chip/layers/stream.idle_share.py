"""Layer device, in a streamed cell: 1 - (union of device-op intervals) /
traced window: how far the host's feed holds the chip back."""


def read(window):
    return None if window.trace is None else window.trace.idle_share
