"""Layer feed (mesh drivers and the host->HBM feed), in a host-fed cell:
seconds per round inside the program's ``pod.wait`` span -- the calling
thread blocked in ``block_until_ready`` while the matrix is laid out and
transferred and the round runs (the span log, host clock); mean over the
rounds."""


def read(window):
    seconds = window.spans.get("pod.wait")
    if seconds is None or not window.attempted:
        return None
    return seconds / window.attempted
