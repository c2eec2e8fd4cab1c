"""Layer wire + store (server/snapshot.py, server/sqlite.py): seconds
per round the server spends freezing the snapshot, transposing it and
enqueuing the clerks' jobs (the span log, host clock)."""

PHASES = ("server.snapshot_freeze", "server.transpose", "server.enqueue_jobs")


def read(window):
    if not window.attempted or not any(p in window.spans for p in PHASES):
        return None
    return sum(window.spans.get(p, 0.0) for p in PHASES) / window.attempted
