"""Layer backend (device rule and compile cache): programs JAX had to obtain
inside the window, compiled or loaded. Expected 0: every shape is warmed in set-up,
so a count here is warm-up that set-up did not do -- it moves ``setup_s``
down and the rounds that compiled up. It does not fail the run."""


def read(window):
    return window.compiles_in_window
