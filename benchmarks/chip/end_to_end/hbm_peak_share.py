"""Peak bytes on the fullest chip (live buffers plus the runtime's
reservation for program temporaries: harness.memory) over its
``bytes_limit``: what "how large a vector fits" costs. From
``memory_stats()`` after the window; it repeats exactly."""


def read(window):
    if not window.memory_limit_bytes:
        return None
    return window.memory_peak_bytes / window.memory_limit_bytes
