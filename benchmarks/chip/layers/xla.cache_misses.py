"""Layer backend (device rule and compile cache): programs that set-up had
to compile because the persistent cache did not hold them. All of them in
a checkout's first run, 0 afterwards."""


def read(window):
    return window.setup_cache["misses"]
