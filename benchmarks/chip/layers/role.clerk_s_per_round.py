"""Layer role code (client/, crypto/): seconds per round in the
clerks' ``clerk.decrypt`` + ``.combine`` + ``.encrypt`` phases, summed
over the clerks' threads (the span log, host clock)."""

PHASES = ("clerk.decrypt", "clerk.combine", "clerk.encrypt")


def read(window):
    if not window.attempted or not any(p in window.spans for p in PHASES):
        return None
    return sum(window.spans.get(p, 0.0) for p in PHASES) / window.attempted
