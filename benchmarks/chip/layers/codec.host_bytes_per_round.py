"""Layer codec (``models/federated.py``): bytes a FedAvg round's entry
point itself moved between host and devices, both ways -- the program's
counters ``models.fedavg.host_bytes`` / ``models.fedavg.rounds``. 0 on the
resident path (nothing of the cohort crosses); on the host path the
float32 deltas up and the int64 aggregate down. Exact integers from
shapes: they repeat from run to run.

The counters are the process's, not the window's: the warm-up round of
set-up is in both, at the cell's own shape. None on a program without
the counters."""


def read(window):
    from sda_tpu.utils import metrics

    counters = metrics.counter_report("models.fedavg.")
    rounds = counters.get("models.fedavg.rounds")
    if not rounds:
        return None
    return counters.get("models.fedavg.host_bytes", 0) / rounds
