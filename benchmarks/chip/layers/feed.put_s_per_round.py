"""Layer feed (mesh drivers and the host->HBM feed), in a host-fed cell:
seconds per round inside the program's ``pod.feed`` span -- how long
``jax.device_put`` of the round's host matrix holds the calling thread
before it returns (the span log, host clock); mean over the rounds."""


def read(window):
    seconds = window.spans.get("pod.feed")
    if seconds is None or not window.attempted:
        return None
    return seconds / window.attempted
