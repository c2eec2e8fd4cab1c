"""Layer role code (client/, crypto/): seconds per round in the
participants' ``participant.mask`` + ``.share`` + ``.encrypt`` phases,
summed over devices and threads (``timed_phase`` registry, host
clock): thread-seconds, so they can exceed the round's wall."""

PHASES = ("participant.mask", "participant.share", "participant.encrypt")


def read(window):
    if not window.attempted or not any(p in window.spans for p in PHASES):
        return None
    return sum(window.spans.get(p, 0.0) for p in PHASES) / window.attempted
