"""Layer wire + store (http/aserver.py, server/sqlite.py): seconds per
round inside the server's ``server.create_participation`` spans, summed
over the server's threads (the span log, host clock). The server runs
in the benchmark's process, so its spans reach the harness's sink."""


def read(window):
    seconds = window.spans.get("server.create_participation")
    if seconds is None or not window.attempted:
        return None
    return seconds / window.attempted
