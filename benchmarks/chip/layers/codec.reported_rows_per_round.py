"""Layer codec (``models/federated.py``): rows of the cohort's buffer that
a FedAvg round was told had reported, a round -- the program's counters
``models.fedavg.reported_rows`` / ``models.fedavg.rounds``. The rows the
mean is over: ``elements_per_s_per_chip`` counts them, and the rest of
the buffer is overhead the fold and the kernel still pay for. Exact
integers from the schedule: they repeat from run to run of one seed.

The counters are the process's, not the window's: the warm-up round of
set-up is in both, with the schedule's first set. None on a program
without the counter (no ``reported`` operand), or in a cell whose rounds
hand none from the host."""


def read(window):
    from sda_tpu.utils import metrics

    counters = metrics.counter_report("models.fedavg.")
    rounds = counters.get("models.fedavg.rounds")
    if not rounds or "models.fedavg.reported_rows" not in counters:
        return None
    return counters["models.fedavg.reported_rows"] / rounds
