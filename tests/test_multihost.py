"""Multi-controller execution: TWO OS processes, each owning 4 CPU devices,
jointly run one SimulatedPod round over gRPC collectives — the same
multi-process code path a real multi-host TPU deployment uses
(mesh/multihost.py). Each process contributes only its process-local
participant rows; both must independently reveal the identical global
aggregate.
"""

import os
import socket
import subprocess
import sys

import pytest

_WORKER = r"""
import sys
import jax

port, pid = sys.argv[1], int(sys.argv[2])
from sda_tpu.mesh import multihost
multihost.initialize(f"localhost:{port}", num_processes=2, process_id=pid)

import numpy as np
from sda_tpu.mesh import SimulatedPod, make_multislice_mesh
from sda_tpu.protocol import FullMasking, PackedShamirSharing

assert jax.process_count() == 2
assert len(jax.devices()) == 8          # global view
assert len(jax.local_devices()) == 4    # this host's slice

scheme = PackedShamirSharing(3, 8, 4, 433, 354, 150)
# one slice block per process: participant data never crosses hosts
mesh = make_multislice_mesh(2, 2, 2)
pod = SimulatedPod(scheme, masking_scheme=FullMasking(433), mesh=mesh)

def rows(process):  # deterministic, RAGGED per-process participant rows
    return np.random.default_rng(100 + process).integers(
        0, 433, size=(2 + process, 12)
    )

out = multihost.aggregate_process_local(
    pod, rows(pid), key=jax.random.PRNGKey(7)
)
expected = (rows(0).sum(axis=0) + rows(1).sum(axis=0)) % 433
np.testing.assert_array_equal(out, expected)

# streamed flagship-scale path: every process streams its own rows in
# tiles; RAGGED local counts (5 rows on process 0, 4 on process 1) and
# several dim tiles
from sda_tpu.mesh import StreamedPod
from sda_tpu.protocol import AdditiveSharing, ChaChaMasking
spod = StreamedPod(
    AdditiveSharing(share_count=8, modulus=433),
    ChaChaMasking(433, 40, 128),
    mesh=mesh, participants_chunk=4, dim_chunk=16,
)
def srows(process):  # ragged: 5 rows on process 0, 4 on process 1
    return np.random.default_rng(900 + process).integers(
        0, 433, size=(5 - process, 40)
    )
mine = srows(pid)
def strict_provider(lp0, lp1, d0, d1):
    # the driver must never ask for rows beyond what THIS process declared
    assert 0 <= lp0 <= lp1 <= mine.shape[0], (lp0, lp1, mine.shape)
    return mine[lp0:lp1, d0:d1]
sout = multihost.streamed_aggregate_process_local(
    spod, strict_provider,
    local_participants=mine.shape[0], dimension=40, key=jax.random.PRNGKey(9),
)
np.testing.assert_array_equal(sout, (srows(0).sum(0) + srows(1).sum(0)) % 433)

# clerk-dropout round (round-2 verdict #6): kill process 1's entire clerk
# contribution. On the (4, 2) mesh, process 1 hosts p-shards 2-3 = clerk
# rows 4..7; with k=2, n=8, t=1 the reconstruction threshold is 3, so the
# finale reveals exactly from process-0-hosted rows alone — no value that
# lives on process 1's devices after the clerk scatter enters the result.
from sda_tpu.fields import numtheory
t2, p2, w22, w32 = numtheory.generate_packed_params(2, 8, 8)
assert t2 + 2 <= 4, "quorum must fit in process 0's clerk rows"
dscheme = PackedShamirSharing(2, 8, t2, p2, w22, w32)
dpod = StreamedPod(
    dscheme, FullMasking(p2), mesh=mesh,
    participants_chunk=4, dim_chunk=16,
    surviving_clerks=(0, 1, 2, 3),  # every row process 0 hosts
)
def drows(process):
    return np.random.default_rng(700 + process).integers(
        0, p2, size=(4, 36)
    )
mine_d = drows(pid)
dout = multihost.streamed_aggregate_process_local(
    dpod, lambda lp0, lp1, d0, d1: mine_d[lp0:lp1, d0:d1],
    local_participants=4, dimension=36, key=jax.random.PRNGKey(13),
)
np.testing.assert_array_equal(dout, (drows(0).sum(0) + drows(1).sum(0)) % p2)
print(f"MULTIHOST_OK process={pid}", flush=True)
"""


def test_two_process_pod_round():
    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]

    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",  # multihost workers are CPU processes
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
        PYTHONPATH=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _WORKER, str(port), str(pid)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        )
        for pid in range(2)
    ]
    outs = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=540)
            outs.append((p.returncode, out, err))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for pid, (rc, out, err) in enumerate(outs):
        assert rc == 0, f"process {pid} failed:\n{err[-3000:]}"
        assert f"MULTIHOST_OK process={pid}" in out


_CK_WORKER = r"""
import os
import sys

import jax

port, pid, attempt, ckdir = (sys.argv[1], int(sys.argv[2]),
                             int(sys.argv[3]), sys.argv[4])
from sda_tpu.mesh import multihost
multihost.initialize(f"localhost:{port}", num_processes=2, process_id=pid)

import numpy as np
from sda_tpu.mesh import StreamedPod, make_multislice_mesh
from sda_tpu.protocol import AdditiveSharing, FullMasking

mesh = make_multislice_mesh(2, 2, 2)
spod = StreamedPod(
    AdditiveSharing(share_count=8, modulus=433), FullMasking(433),
    mesh=mesh, participants_chunk=4, dim_chunk=16,
)

def rows(process):
    return np.random.default_rng(40 + process).integers(0, 433, size=(8, 48))

mine = rows(pid)
calls = {"n": 0}

def provider(lp0, lp1, d0, d1):
    calls["n"] += 1
    if attempt == 0 and calls["n"] > 4:
        # simulate the fleet dying mid-round (both ranks hit the same
        # lockstep tile, like a preemption)
        os._exit(3)
    return mine[lp0:lp1, d0:d1]

out = multihost.streamed_aggregate_process_local(
    spod, provider, local_participants=8, dimension=48,
    key=jax.random.PRNGKey(21),
    checkpoint_path=f"{ckdir}/ck", checkpoint_every_chunks=1,
)
np.testing.assert_array_equal(out, (rows(0).sum(0) + rows(1).sum(0)) % 433)
print(f"CK_OK rank={pid} calls={calls['n']}", flush=True)
"""


def _launch_ck_workers(port, attempt, ckdir):
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",  # multihost workers are CPU processes
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
        PYTHONPATH=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    return [
        subprocess.Popen(
            [sys.executable, "-c", _CK_WORKER, str(port), str(pid),
             str(attempt), str(ckdir)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        )
        for pid in range(2)
    ]


def test_multihost_streamed_checkpoint_resume(tmp_path):
    """The fleet dies mid-round; a relaunch resumes from the coordinated
    per-rank snapshots and reveals EXACTLY — including the staggered case
    where one rank's newest snapshot is lost (its slot file deleted, as
    if that rank crashed before its last save landed): every rank falls
    back to the newest cursor all of them still hold."""
    import numpy as np

    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]

    # attempt 0: both ranks die after 4 provider calls. The first exit
    # can kill the peer through the coordination service (rc 1,
    # "connection reset") before it reaches its own os._exit(3) — either
    # death is a valid mid-round crash, and any cursor spread it leaves
    # is what the two-slot history exists for.
    procs = _launch_ck_workers(port, 0, tmp_path)
    for p in procs:
        out, err = p.communicate(timeout=540)
        assert p.returncode != 0, (p.returncode, err[-2000:])

    # simulate rank 1 having crashed BEFORE its newest save landed: drop
    # its newest slot — but only when the surviving (older) cursor still
    # exists in rank 0's history, else the two-slot spread is exceeded
    # and the fleet would (correctly) restart from scratch, which is not
    # the path under test
    def cursor(path):
        with np.load(path) as z:
            return (int(z["di"]), int(z["pi"]), int(z["done_dims"]))

    def rank_slots(rank):
        return [p for p in (tmp_path / f"ck.r{rank}of2.{s}" for s in "ab")
                if p.exists()]

    assert rank_slots(1), "rank 1 saved no snapshot"
    if len(rank_slots(1)) == 2:
        older, newest = sorted(rank_slots(1), key=cursor)
        if cursor(older) in {cursor(p) for p in rank_slots(0)}:
            newest.unlink()
    # resume is possible iff some cursor exists in both ranks' histories
    common = ({cursor(p) for p in rank_slots(0)}
              & {cursor(p) for p in rank_slots(1)})
    resume_expected = bool(common)

    # attempt 1: fresh processes resume and finish exactly
    with socket.socket() as s:
        s.bind(("localhost", 0))
        port2 = s.getsockname()[1]
    procs = _launch_ck_workers(port2, 1, tmp_path)
    full_calls = (16 // 4) * (48 // 16)  # p-tiles x d-tiles = 12
    for pid, p in enumerate(procs):
        out, err = p.communicate(timeout=540)
        assert p.returncode == 0, f"rank {pid} failed:\n{err[-3000:]}"
        assert f"CK_OK rank={pid}" in out
        calls = int(out.split("calls=")[1].split()[0])
        if resume_expected:
            assert calls < full_calls, (calls, full_calls)
        else:  # coordinated restart: still exact, full provider sweep
            assert calls == full_calls, (calls, full_calls)

    # snapshots removed on completion
    leftovers = list(tmp_path.glob("ck.r*"))
    assert not leftovers, leftovers


_QUAD_WORKER = r"""
import os
import sys

import jax

port, pid, attempt, ckdir = (sys.argv[1], int(sys.argv[2]),
                             int(sys.argv[3]), sys.argv[4])
from sda_tpu.mesh import multihost
multihost.initialize(f"localhost:{port}", num_processes=4, process_id=pid)

import numpy as np
from sda_tpu.mesh import StreamedPod, make_multislice_mesh
from sda_tpu.protocol import AdditiveSharing, ChaChaMasking

assert jax.process_count() == 4
assert len(jax.devices()) == 8          # global view
assert len(jax.local_devices()) == 2    # this host's slice

# FOUR slices of (1 participant-shard x 2 dim-shards): every process owns
# exactly one slice, so the per-stage 'd' collectives stay inside a slice
# (ICI) and only the participant fold crosses the four slice boundaries
# (DCN) — the SURVEY §5.8 layout rule at fleet width.
mesh = make_multislice_mesh(4, 1, 2)
spod = StreamedPod(
    AdditiveSharing(share_count=8, modulus=433),
    ChaChaMasking(433, 48, 128),
    mesh=mesh, participants_chunk=4, dim_chunk=16,
)

def rows(process):  # ragged local counts: 3/2/2/2 rows across the ranks
    return np.random.default_rng(500 + process).integers(
        0, 433, size=(2 + (process == 0), 48)
    )

mine = rows(pid)
calls = {"n": 0}

def provider(lp0, lp1, d0, d1):
    assert 0 <= lp0 <= lp1 <= mine.shape[0], (lp0, lp1, mine.shape)
    calls["n"] += 1
    if attempt == 0 and calls["n"] > 2 + pid:
        # STAGGERED loss: each rank dies at a different tile count, so the
        # surviving snapshot histories genuinely disagree (rank 0 first;
        # its death may also kill peers through the coordination service
        # before they reach their own limits — any spread is valid)
        os._exit(3)
    return mine[lp0:lp1, d0:d1]

out = multihost.streamed_aggregate_process_local(
    spod, provider, local_participants=mine.shape[0], dimension=48,
    key=jax.random.PRNGKey(33),
    checkpoint_path=f"{ckdir}/qk", checkpoint_every_chunks=1,
)
expected = sum(rows(r).sum(axis=0) for r in range(4)) % 433
np.testing.assert_array_equal(out, expected)
print(f"QUAD_OK rank={pid} calls={calls['n']}", flush=True)
"""


def _launch_quad_workers(port, attempt, ckdir):
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",  # multihost workers are CPU processes
        XLA_FLAGS="--xla_force_host_platform_device_count=2",
        PYTHONPATH=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    return [
        subprocess.Popen(
            [sys.executable, "-c", _QUAD_WORKER, str(port), str(pid),
             str(attempt), str(ckdir)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        )
        for pid in range(4)
    ]


def test_four_process_multislice_staggered_loss_resume(tmp_path):
    """Fleet-width evidence in one test (round-4 verdict #6): FOUR
    processes over a 4-slice multislice mesh run a streamed ChaCha round,
    die with STAGGERED per-rank cursors mid-round (plus one rank's newest
    snapshot deleted, as if it crashed before the save landed), and a
    full relaunch resumes from the newest cursor common to all four
    histories — or restarts cleanly when none exists — revealing the
    exact aggregate either way."""
    import numpy as np

    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]

    procs = _launch_quad_workers(port, 0, tmp_path)
    for p in procs:
        out, err = p.communicate(timeout=540)
        assert p.returncode != 0, (p.returncode, err[-2000:])

    def cursor(path):
        with np.load(path) as z:
            return (int(z["di"]), int(z["pi"]), int(z["done_dims"]))

    def rank_slots(rank):
        return [p for p in (tmp_path / f"qk.r{rank}of4.{s}" for s in "ab")
                if p.exists()]

    assert any(rank_slots(r) for r in range(4)), "no rank saved a snapshot"
    # simulate rank 3 crashing before its newest save landed — but only
    # when dropping it still leaves a cursor shared with every other rank,
    # else the (correct) from-scratch restart path would be exercised
    # instead of the resume under test
    slots3 = rank_slots(3)
    if len(slots3) == 2:
        older, newest = sorted(slots3, key=cursor)
        if all(cursor(older) in {cursor(p) for p in rank_slots(r)}
               for r in range(3)):
            newest.unlink()
    histories = [{cursor(p) for p in rank_slots(r)} for r in range(4)]
    resume_expected = bool(set.intersection(*histories)) if all(
        histories) else False

    with socket.socket() as s:
        s.bind(("localhost", 0))
        port2 = s.getsockname()[1]
    procs = _launch_quad_workers(port2, 1, tmp_path)
    # lockstep tile schedule: global p-tiles x d-tiles with the GLOBAL
    # participant count padded to the chunk (3+2+2+2=9 -> 12/4=3 p-tiles,
    # 48/16=3 d-tiles)
    full_calls = 3 * 3
    for pid, p in enumerate(procs):
        out, err = p.communicate(timeout=540)
        assert p.returncode == 0, f"rank {pid} failed:\n{err[-3000:]}"
        assert f"QUAD_OK rank={pid}" in out
        calls = int(out.split("calls=")[1].split()[0])
        if resume_expected:
            assert calls < full_calls, (calls, full_calls)
        else:
            assert calls == full_calls, (calls, full_calls)

    leftovers = list(tmp_path.glob("qk.r*"))
    assert not leftovers, leftovers
