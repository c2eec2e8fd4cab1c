"""Driver ``pod_fedavg_sporadic``: ``pod_fedavg``'s round -- float32 client
weights that stay in HBM, one device program from the delta to the new
global vector -- over a cohort whose reporters differ every round. The
buffer holds the selected cohort, ``pod_fedavg_round(...,
reported=<bool[participants]>)`` says which of its rows count, and the
mean is over them: one compiled program for every set.

The traffic file states the buffer (``resident-f32-1200x1m``'s law: the
global vector uniform in (-1, 1), a client its global vector plus a
standard normal delta, a key a row) and the schedule: ``sets`` reporter
sets drawn from the seed, their counts distinct and uniform between
``participants / over_selection`` (rounded down) and ``participants``
(one from each of ``sets`` equal strata: :func:`reporter_sets`), each a
uniform random subset of the rows. Round ``i`` uses set ``i mod
sets`` and a fresh key. The rows that do not report keep their weights,
which look like any other row's: a program that sums them, or divides by
the buffer's rows, is some ten thousand limits off.

Set-up, in this order, so that nothing of it stands on top of the round's
arrays in the memory peak ``hbm_peak_share`` reads:

1. it fails at once, with nothing on the device, on a tree whose
   ``pod_fedavg_round`` takes no ``reported``: there every count of
   reporters is a slice of another shape, a compile a round;
2. the integer stage, held exactly: the cohort's first ``CHECKED_ROWS``
   rows under set 0's entries for them, through ``codec.encode_device``
   and ``pod.aggregate_fn(..., reported=True)``, against the reference's
   integer sum bit for bit, and the round's count against the set's;
3. the whole cohort; for every set the reference's integer sum on the
   device (one compile, the set an argument), its float64 end on the host;
   the expected vectors and tolerances back on the device, an array a set;
4. ONE warm-up round, with set 0, held to the reference. The window then
   meets sets the program has never seen.

``failed`` counts the rounds outside the tolerance, the rounds that raised
(the harness's) and every compile request between the end of set-up and
``finish()``: one program serves every set, or the cell fails.

It keys the compile cache on op metadata too (``drivers/pod_fedavg.py``
says why).
"""

from __future__ import annotations

#: rows of the cohort whose integer aggregate set-up checks exactly
CHECKED_ROWS = 96


def reporter_sets(seed: int, participants: int, sets: int, fewest: int):
    """-> bool ``[sets, participants]``: ``sets`` distinct counts over
    ``fewest .. participants``, each set a uniform random subset of the
    rows of its size.

    The counts are a stratified draw: the range is cut into ``sets`` equal
    strata, one count is drawn from each, and their order is shuffled. A
    set's count is uniform over the range, the counts are distinct, and
    the schedule's mean -- which ``elements_per_s_per_chip`` counts, as
    reporters x dim -- is the same to a part in a thousand on every seed
    (32 independent draws moved it by 2 %, four times the metric's bound:
    my chip runs, PR 44)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    edges = np.ceil(np.linspace(fewest, participants + 1, sets + 1)).astype(int)
    counts = rng.permutation(rng.integers(edges[:-1], edges[1:]))
    reported = np.zeros((sets, participants), dtype=bool)
    for row, count in zip(reported, counts):
        row[rng.choice(participants, size=count, replace=False)] = True
    return reported


class PodFedAvgSporadic:
    def __init__(self, cell, seed: int, devices, rehearsal: bool):
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import NamedSharding, PartitionSpec

        from harness import CompileCounter, load_module, log
        from sda_tpu.models import pod_fedavg_round

        config, traffic = cell.config, cell.traffic
        if traffic["input"] != "resident" or traffic["dtype"] != "float32":
            raise ValueError(
                "driver 'pod_fedavg_sporadic' runs float32 weights resident "
                f"on the device; the traffic states {traffic['dtype']!r}, "
                f"{traffic['input']!r}")
        participants, dim = traffic["participants"], traffic["dim"]
        sets = traffic["sets"]
        fewest = int(participants / traffic["over_selection"])
        if participants - fewest + 1 < sets:
            raise ValueError(f"{sets} sets of distinct counts do not fit "
                             f"between {fewest} and {participants} reporters")
        build_pod = load_module(cell.home, "drivers", "pod_fedavg").build_pod
        self.pod, self.codec = build_pod(config, devices, interpret=rehearsal)
        pod, codec, scheme = self.pod, self.codec, self.pod.scheme
        if participants > codec.max_summands:
            raise ValueError(f"{participants} rows exceed the codec's "
                             f"{codec.max_summands} summands")
        reference = load_module(cell.home, "references", config["reference"])
        stated = (codec.modulus, codec.clip, codec.fractional_bits)
        mesh = pod.mesh
        rows_sharded = NamedSharding(mesh, PartitionSpec("p", "d"))
        dim_sharded = NamedSharding(mesh, PartitionSpec("d"))
        everywhere = NamedSharding(mesh, PartitionSpec())

        # the harness's counter of programs obtained, compiled or loaded: its
        # own feeds xla.compiles_in_window, which fails no run; this one does
        self.compiles = CompileCounter()
        self.key = jax.random.PRNGKey(seed)
        self.fold_in = jax.random.fold_in
        data_key = jax.random.fold_in(self.key, 0x1A7A)
        self.reported = reporter_sets(seed, participants, sets, fewest)
        counts = self.reported.sum(axis=1)

        @jax.jit
        def make_global(key):
            return jax.random.uniform(key, (dim,), jnp.float32, -1.0, 1.0)

        def make_clients(rows):
            def make(key, global_vec):
                keys = jax.vmap(lambda row: jax.random.fold_in(key, row))(
                    jnp.arange(rows))
                deltas = jax.vmap(
                    lambda k: jax.random.normal(k, (dim,), jnp.float32))(keys)
                return global_vec[None, :] + deltas
            return jax.jit(make, out_shardings=rows_sharded)

        integer_sum = jax.jit(
            lambda g, c, who: reference.integer_sum(g, c, who, *stated, xp=jnp))

        self.global_vec = jax.device_put(
            make_global(jax.random.fold_in(data_key, 0)), dim_sharded)
        client_key = jax.random.fold_in(data_key, 1)

        # the integer stage, exactly, on the first rows alone: while the
        # cohort is not there, the check's arrays set no memory peak
        checked = min(CHECKED_ROWS, participants)
        head = make_clients(checked)(client_key, self.global_vec)
        padded = pod.padded_shape(checked, dim)
        residues = jax.jit(
            lambda g, c: jnp.pad(
                codec.encode_device(c - g[None, :]),
                ((0, padded[0] - checked), (0, padded[1] - dim))),
            out_shardings=rows_sharded)(self.global_vec, head)
        who = np.zeros(padded[0], dtype=bool)
        who[:checked] = self.reported[0, :checked]
        revealed, count = pod.aggregate_fn(*padded, reported=True)(
            residues, self.fold_in(self.key, 0), who)
        if int(count) != int(who.sum()) or not bool(jnp.array_equal(
                revealed[:dim],
                integer_sum(self.global_vec, head, who[:checked]))):
            raise RuntimeError(
                f"the round's integer aggregate of the first {checked} rows "
                "is not the reference's sum of the quantized deltas of "
                "those that reported, or its count is not theirs")
        del head, residues, revealed

        self.clients = make_clients(participants)(client_key, self.global_vec)
        # a vector and a tolerance a set, each an array of its own: handed
        # whole to one compiled check (a float64 argument is split into its
        # halves on the way in, and a stack of them would be split whole,
        # 256 MB a round)
        global_host = np.asarray(self.global_vec)
        self.expected, self.limits = [], []
        for who, count in zip(self.reported, counts):
            total = integer_sum(self.global_vec, self.clients, who)
            exact, mean = reference.new_global(
                global_host, np.asarray(total), int(count), codec.modulus,
                codec.fractional_bits)
            self.expected.append(
                jax.device_put(exact.astype(np.float32), dim_sharded))
            self.limits.append(jax.device_put(
                reference.tolerance(global_host, mean), dim_sharded))

        def check(tally, out, want, limit):
            outside, differ, share = reference.outside(out, want, limit, xp=jnp)
            return (tally[0] + (outside > 0), tally[1] + differ,
                    jnp.maximum(tally[2], share))

        # the tally has one sharding from the start, so the check compiles once
        self.tally = jax.device_put(
            (jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int64),
             jnp.zeros((), jnp.float64)), everywhere)
        self.check = jax.jit(check, out_shardings=everywhere)
        self.fedavg_round = pod_fedavg_round
        self.log = log
        self.out = None
        self.warm = None  # compile requests when set-up ended
        self.facts = {
            "participants": participants, "dim": dim,
            "padded": list(pod.padded_shape(participants, dim)),
            # useful work: the rows that reported, at the schedule's mean
            "elements_per_round": float(counts.mean()) * dim,
            "reporter_sets": sets,
            "reporters": [int(counts.min()), int(counts.max())],
            "input_itemsize": 4,   # the fold reads every row of the buffer
            "secret_count": scheme.secret_count,
            "share_count": scheme.share_count,
            "mesh": list(mesh.devices.shape),
            "pallas_active": pod.pallas_active,
            "cost_model": "pod_round",
        }
        # warm the one shape (compiles or loads from the cache) with set 0,
        # and hold that round to the reference before any round is timed
        self.round(-1)
        self.verify(-1)
        if self.finish():
            raise RuntimeError("the warm-up round is not the reference's "
                               "new global vector")
        self.warm = self.compiles.requests

    def round(self, index: int) -> None:
        key = self.fold_in(self.key, index + 1)  # a fresh key every round
        who = self.reported[max(index, 0) % len(self.reported)]
        self.out = self.fedavg_round(self.pod, self.codec, self.global_vec,
                                     self.clients, key, reported=who)
        self.out.block_until_ready()

    def verify(self, index: int) -> None:
        # stays on the device: one tally, read once after the window
        which = max(index, 0) % len(self.reported)
        self.tally = self.check(self.tally, self.out, self.expected[which],
                                self.limits[which])

    def finish(self) -> int:
        """Rounds with an element outside the reference's tolerance, and
        the programs obtained since set-up ended."""
        failed, differ, share = (float(t) for t in self.tally)
        compiled = 0 if self.warm is None else self.compiles.requests - self.warm
        self.log(f"fedavg check: {int(failed)} round(s) outside the "
                 f"tolerance; {int(differ)} element(s) differ from the "
                 f"reference at all, the furthest at {share:.4f} of its "
                 f"limit; {compiled} program(s) obtained after set-up")
        return int(failed) + compiled

    def close(self) -> None:
        self.clients = self.global_vec = self.expected = self.limits = None
        self.out = None


def setup(cell, seed: int, devices, rehearsal: bool) -> PodFedAvgSporadic:
    import inspect

    import jax

    from sda_tpu.models import pod_fedavg_round

    if "reported" not in inspect.signature(pod_fedavg_round).parameters:
        # before anything is on the device: without the operand a round
        # over 1137 reporters is a slice of another shape than one over
        # 1192, and every round of the window would compile
        raise SystemExit(
            "driver 'pod_fedavg_sporadic' needs pod_fedavg_round(..., "
            "reported=...): on this tree a round cannot be told which rows "
            "of the buffer reported")
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    return PodFedAvgSporadic(cell, seed, devices, rehearsal)
