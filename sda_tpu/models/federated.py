"""Secure federated averaging over the SDA stack.

This is the reference's raison d'etre run end-to-end: each participant
trains locally, and only *encoded model deltas* leave the device — masked,
secret-shared across the committee, and revealed as an exact sum by the
recipient (participate.rs:37-113 / clerk.rs:63-107 / receive.rs:80-157 flow).
No individual update is ever visible to the server or any quorum smaller
than the scheme's privacy threshold.

Two execution surfaces, same math:

- ``FederatedSession`` — the real protocol: an `SdaService` (any store or
  the HTTP seam), one aggregation per round, clerks running chores.
- ``pod_fedavg_round`` — the TPU-native fast path: deltas for a whole
  cohort live as a [P, d] device array and one `SimulatedPod`/
  `StreamedPod` round produces the sum via mesh collectives. Client
  vectors that are already a ``jax.Array`` never leave the devices:
  delta, encode, round, decode and the new global vector are one program.

The fixed-point codec guarantees the secure sum equals the plaintext sum
of quantized deltas bit-for-bit, so FedAvg here is exactly FedAvg — the
only deviation from float averaging is the quantization step itself.
"""

from __future__ import annotations

from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np

from ..protocol import Aggregation, AggregationId
from ..utils import metrics, timed_phase
from .encoding import FixedPointCodec, ravel_pytree

__all__ = ["LocalTrainer", "FederatedSession", "pod_fedavg_round"]


class LocalTrainer:
    """Jitted local-steps trainer: params -> params after k optimizer steps.

    ``loss_fn(params, batch) -> scalar`` and an optax optimizer; the k-step
    loop is a `lax.scan` so one compiled program covers the whole local
    epoch regardless of k (no per-step dispatch).
    """

    def __init__(self, loss_fn: Callable, optimizer):
        import jax
        import jax.numpy as jnp
        import optax

        self.loss_fn = loss_fn
        self.optimizer = optimizer

        def fit(params, opt_state, batches):
            def step(carry, batch):
                p, s = carry
                loss, grads = jax.value_and_grad(loss_fn)(p, batch)
                updates, s = optimizer.update(grads, s, p)
                p = optax.apply_updates(p, updates)
                return (p, s), loss

            (params, opt_state), losses = jax.lax.scan(
                step, (params, opt_state), batches)
            return params, opt_state, jnp.mean(losses)

        # devprof registry: a cohort whose batch shapes drift (ragged local
        # datasets) retraces this program once per shape — the compiled-
        # shape registry and retrace span events make that visible instead
        # of silently serializing compile time into the round
        from ..obs import devprof

        self._fit = devprof.instrument("models.local_fit", jax.jit(fit))

    def init_state(self, params):
        return self.optimizer.init(params)

    def fit(self, params, opt_state, batches):
        """batches: pytree of arrays with a leading [k, ...] steps axis."""
        return self._fit(params, opt_state, batches)


class FederatedSession:
    """Drives secure FedAvg rounds through the real protocol stack.

    The caller supplies ready SdaClients (recipient with an uploaded
    encryption key, clerks with keys, participants) and an Aggregation
    *template* whose schemes/modulus/dimension describe the update vector;
    each round clones it under a fresh id (aggregations are one-shot,
    resources.rs:44-67).
    """

    def __init__(self, template: Aggregation, codec: FixedPointCodec,
                 recipient, clerks: Sequence, participants: Sequence):
        if template.vector_dimension <= 0:
            raise ValueError("template.vector_dimension must be positive")
        if template.modulus != codec.modulus:
            raise ValueError(
                f"codec modulus {codec.modulus} != aggregation modulus "
                f"{template.modulus}: the decoded mean would be garbage")
        if len(participants) > codec.max_summands:
            raise ValueError(
                f"{len(participants)} participants exceed the codec capacity "
                f"{codec.max_summands}")
        self.template = template
        self.codec = codec
        self.recipient = recipient
        self.clerks = list(clerks)
        self.participants = list(participants)

    def round(self, deltas: Sequence[np.ndarray], *,
              deadline: float = 60.0) -> np.ndarray:
        """One secure round: encode + participate + clerk + reveal.

        ``deltas`` is one float vector per participant (client_params -
        global_params, pre-raveled). Returns the exact decoded *mean* delta.

        The encoded int64 residue array is handed to ``participate``
        as-is — the client normalizes ndarrays without a per-element
        Python conversion, so a 10^5-dim model costs one vectorized
        pass, not 10^5 ``int()`` calls (sda_tpu/loadgen/inputbench.py
        measures the difference).

        The reveal is driven through the lifecycle plane
        (:meth:`SdaClient.await_result`): a round the supervisor
        declared terminal raises the typed
        :class:`~sda_tpu.protocol.RoundFailed` /
        :class:`~sda_tpu.protocol.RoundExpired` with the server's
        diagnosis, and a quorum-degraded Shamir round reveals bit-exactly
        from the survivors — never a hang, never a silent partial-
        committee sum. The mean divides by the *revealed* participation
        count (the snapshot's frozen set), so a round whose committee
        degraded still averages over exactly the participations it
        actually summed. ``deadline`` bounds the wait client-side.
        """
        if len(deltas) != len(self.participants):
            raise ValueError("one delta per participant required")
        dim = self.template.vector_dimension
        aggregation = self.template.replace(id=AggregationId.random())
        self.recipient.upload_aggregation(aggregation)
        self.recipient.begin_aggregation(aggregation.id)

        for participant, delta in zip(self.participants, deltas):
            delta = np.asarray(delta, dtype=np.float64)
            if delta.shape != (dim,):
                raise ValueError(f"delta shape {delta.shape} != ({dim},)")
            participant.participate(self.codec.encode(delta), aggregation.id)

        self.recipient.end_aggregation(aggregation.id)
        self.recipient.run_chores(-1)
        for clerk in self.clerks:
            clerk.run_chores(-1)

        output = self.recipient.await_result(
            aggregation.id, deadline=deadline, poll_interval=0.05)
        values = output.positive().values
        # None = pre-lifecycle server: fall back to the nominal count. A
        # REVEALED 0 is a real (degenerate) answer — let decode_mean's
        # typed empty-summand guard surface it rather than silently
        # averaging an empty sum over the full population.
        summands = (output.participations
                    if output.participations is not None
                    else len(self.participants))
        return self.codec.decode_mean(values, summands)


def _resident_program(pod, codec: FixedPointCodec, participants: int,
                      dimension: int, with_aggregate: bool = False,
                      reported: bool = False):
    """The FedAvg round on arrays the devices hold, as one program of the
    pod: ``program(global_vec [d], client_vecs [P, d], key)`` -> the new
    global vector [d] float32 (with ``with_aggregate``, a test's: the
    round's int64 aggregate beside it, built anew). Built once per (pod,
    codec, shape) by ``pod.round_program`` and kept with the pod; it
    carries the round's ``pod.dispatch`` span and counters.

    With ``reported`` the program takes a fourth operand, ``[P]`` bool:
    the rows of the buffer that count this round. It is a value the
    program reads, so one program serves every set of reporters over a
    buffer of ``participants`` rows (the key holds the buffer's rows, no
    count). It selects among the *residues*, inside the round's fold: the
    encode has already scrubbed NaN and clipped, so whatever a row that
    did not report holds reaches nothing; the mean divides by the round's
    count of reporters, and with none the global vector comes back as it
    went in.

    Two stage scopes around the round's own: ``sda.encode`` -- the deltas
    ``client - global`` in float32, their fixed-point residues, and the
    zero rows and columns up to the pod's grain, which are residue 0 -- and
    ``sda.decode`` -- the padding stripped, the centered lift, the mean,
    the add to the global vector. The residues are canonical, so they go in
    as uint32 and the round's residue pass is the canon alone.
    """
    import jax
    from jax import numpy as jnp

    rows, width = pod.padded_shape(participants, dimension)
    pad = ((0, rows - participants), (0, width - dimension))

    def around(round_):
        def program(global_vec, client_vecs, key, *who):
            with jax.named_scope("sda.encode"):
                global_vec = global_vec.astype(jnp.float32)
                deltas = client_vecs.astype(jnp.float32) - global_vec[None, :]
                residues = codec.encode_device(deltas).astype(jnp.uint32)
                if pad != ((0, 0), (0, 0)):
                    residues = jnp.pad(residues, pad)
                if reported:  # the padding rows did not report
                    who = (jnp.pad(who[0], pad[0]),)
            if reported:
                aggregate, count = round_(residues, key, *who)
            else:
                aggregate, count = round_(residues, key), participants
            with jax.named_scope("sda.decode"):
                aggregate = aggregate[:dimension]
                new_global = global_vec + codec.decode_mean_device(
                    aggregate, count, capacity=participants)
            return (new_global, aggregate) if with_aggregate else new_global

        return program

    key = None if with_aggregate else (
        codec.modulus, codec.fractional_bits, codec.clip, participants,
        dimension, reported)
    return pod.round_program(rows, width, around, "models.fedavg.round", key,
                             reported=reported)


def pod_fedavg_round(pod, codec: FixedPointCodec, global_vec, client_vecs,
                     key=None, reported=None):
    """TPU-native FedAvg round: cohort deltas -> mesh round -> mean delta.

    ``client_vecs`` is a [P, d] float array (or list of vectors) of client
    parameter vectors; deltas against ``global_vec`` are encoded on device
    and aggregated in ONE pod round (mask + share + psum_scatter + finale
    all via mesh collectives — no per-client protocol messages). Returns the
    new global vector, exactly global + mean(quantized deltas)/scale.

    ``reported`` ([P] of 0/1: NumPy or a sequence, as the coordinator knows
    who reported, or a ``jax.Array``) says which rows of the buffer count
    this round: hand the round the selected cohort's whole buffer and who
    reported, never a slice of it. A row whose entry is 0 changes no bit of
    the result whatever it holds (stale weights, NaN, +-inf), and the mean
    is over the rows that reported; with none, the global vector is
    returned as it is. The buffer's rows, not the count, must fit the
    codec's ``max_summands``. ``None`` is a round in which every row
    reported.

    Two contracts, by where the cohort lives:

    - **resident** — ``client_vecs`` is a ``jax.Array`` and the pod has a
      traceable round (``SimulatedPod``): delta, encode, the round, decode
      and ``global + mean`` are ONE jitted program on the pod's mesh, all
      in float32, and the result is a float32 ``jax.Array`` that is not
      waited for. Nothing of the cohort crosses to the host (a
      ``global_vec`` or a ``reported`` given from the host is put on the
      devices, and counted). ``reported`` is an operand of that program,
      not a shape: every set of reporters runs the one program compiled
      for the buffer. Against the host contract: the deltas are formed in
      float32, not float64, and the mean is the host's rounded to float32
      to within ``2^-23 |mean|`` (``FixedPointCodec.decode_mean_device``).
    - **host** — anything else: the deltas are subtracted in float64 on
      the host and sent to the devices once as float32, encoded there and
      handed to ``pod.aggregate`` as they are (with ``reported``, which
      the streamed drivers refuse); the aggregate is fetched
      and decoded in NumPy float64 over the host's count, and the result
      is a NumPy float64 vector. Every surface with ``aggregate(inputs,
      key)`` is served (``StreamedPod``, ``StreamingAggregator``: they
      take their inputs to the host themselves).

    Either way one ``fedavg.round`` phase is timed around it (attributes
    ``participants``, ``dimension``, ``resident``, and with the operand
    given from the host ``reported``, its count), and
    ``models.fedavg.rounds`` / ``models.fedavg.host_bytes`` count the
    rounds and the bytes this function itself moved between host and
    devices, both ways: 0 on the resident path but for such operands;
    ``models.fedavg.reported_rows`` counts the rows a host ``reported``
    said had reported.
    """
    import jax
    from jax import numpy as jnp

    resident = (isinstance(client_vecs, jax.Array)
                and hasattr(pod, "round_program"))
    moved = 0  # bytes between host and devices, by this function
    if not resident:
        moved = sum(v.nbytes for v in (global_vec, client_vecs, reported)
                    if isinstance(v, jax.Array))
        global_vec = np.asarray(global_vec, dtype=np.float64)
        client_vecs = np.asarray(client_vecs, dtype=np.float64)
        if reported is not None:
            reported = np.asarray(reported)
    shape, dim = np.shape(client_vecs), np.shape(global_vec)
    if len(shape) != 2 or len(dim) != 1 or shape[1] != dim[0]:
        raise ValueError(f"client_vecs shape {shape} incompatible "
                         f"with global {dim}")
    n = shape[0]
    if n > codec.max_summands:
        raise ValueError(f"{n} clients exceed codec capacity {codec.max_summands}")
    pod_modulus = getattr(pod, "modulus", codec.modulus)
    if pod_modulus != codec.modulus:
        raise ValueError(
            f"codec modulus {codec.modulus} != pod modulus {pod_modulus}: "
            "the decoded mean would be garbage")
    attributes = dict(participants=n, dimension=dim[0], resident=resident)
    if reported is not None:
        if np.shape(reported) != (n,):
            raise ValueError(f"reported has shape {np.shape(reported)}; the "
                             f"cohort has {n} rows")
        if not isinstance(reported, jax.Array):
            # the coordinator's list: counted here, where it is on the host
            reported = np.asarray(reported).astype(bool)
            attributes["reported"] = int(reported.sum())
            metrics.count("models.fedavg.reported_rows", attributes["reported"])

    metrics.count("models.fedavg.rounds")
    with timed_phase("fedavg.round") as phase:
        phase.attributes.update(attributes)
        if resident:
            if not isinstance(global_vec, jax.Array):
                global_vec = jnp.asarray(global_vec, jnp.float32)
                moved += global_vec.nbytes
            who = ()
            if reported is not None:
                if isinstance(reported, jax.Array):
                    reported = reported.astype(bool)
                else:
                    # the program's own call puts the list on the devices,
                    # with its other operands: no transfer of its own
                    # stands in front of the dispatch (the round waits for
                    # it: the fold reads it first)
                    moved += reported.nbytes
                who = (reported,)
            if key is None:
                from ..crypto.core import fresh_prng_key

                key = fresh_prng_key()
            metrics.count("models.fedavg.host_bytes", moved)
            return _resident_program(
                pod, codec, n, dim[0], reported=bool(who))(
                    global_vec, client_vecs, key, *who)
        deltas = jnp.asarray(client_vecs - global_vec[None, :], jnp.float32)
        encoded = codec.encode_device(deltas)
        if reported is None:
            summed, count = np.asarray(pod.aggregate(encoded, key)), n
        else:
            summed = np.asarray(pod.aggregate(encoded, key, reported=reported))
            count = attributes["reported"]
            moved += reported.nbytes
        metrics.count("models.fedavg.host_bytes",
                      moved + deltas.nbytes + summed.nbytes)
        if count == 0:  # nobody reported: the global vector holds
            return global_vec
        return global_vec + codec.decode_mean(summed, count)
