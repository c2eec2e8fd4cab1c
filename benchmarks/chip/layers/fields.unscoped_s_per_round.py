"""Layer fields (field kernels): device seconds per round in which an op
ran inside the round and none under a stage scope did (the list:
``reduce/stages.py::STAGES``, docs/observability.md) -- what the compiler
makes under no name of the program's, and a scan's own bookkeeping where
the scan stands under no stage. With the seconds under the stage scopes
it closes on ``fields.device_s_per_round`` (+ the collectives').
Instants, not ops (reduce/stages.py). Median over the traced rounds. None
in an untraced run and where no op carries a stage scope."""

from reduce import stages


def read(window):
    return stages.remainder_per_round(
        window, stages.anything, stages.under(*stages.STAGES))
