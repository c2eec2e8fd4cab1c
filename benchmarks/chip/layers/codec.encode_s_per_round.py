"""Layer codec (models/encoding.py): seconds a round spends in
``codec.encode`` for all its devices, from the driver's ``codec.encode``
span around the calls (the span log, host clock); mean over the rounds."""


def read(window):
    seconds = window.spans.get("codec.encode")
    if seconds is None or not window.attempted:
        return None
    return seconds / window.attempted
