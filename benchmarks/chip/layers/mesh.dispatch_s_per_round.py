"""Layer mesh (mesh drivers), in a resident cell: seconds per round inside
the program's ``pod.dispatch`` span -- the call of the jitted round from
entry until the asynchronous dispatch returns, on however many chips (the
span log, host clock); mean over the rounds."""


def read(window):
    seconds = window.spans.get("pod.dispatch")
    if seconds is None or not window.attempted:
        return None
    return seconds / window.attempted
