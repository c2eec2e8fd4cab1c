"""Layer mesh (``mesh/simpod.py``): round programs the process built --
the program's counter ``mesh.round.builds``, counted in
``SimulatedPod._build`` (every ``aggregate_fn``, every new key of
``round_program``, every new shape of ``aggregate``). What an operator
watches to see the cliff: a build is a trace and an XLA compile, seconds
against a round's milliseconds. Set-up's number, whatever the window
did: a cell whose reporters differ every round reads what a cell of one
fixed cohort reads, or a count became a shape again.

The process's, warm-up included. None on a program without the counter."""


def read(window):
    from sda_tpu.utils import metrics

    return metrics.counter_report("mesh.round.").get("mesh.round.builds")
