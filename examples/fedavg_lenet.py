"""Secure federated averaging of a real LeNet, end to end.

Four clients train locally on synthetic MNIST-shaped data; only
fixed-point-encoded model deltas are aggregated — masked, secret-shared
across an 8-clerk committee on a device mesh, and revealed as an exact
sum. No individual update ever leaves a client in the clear.

A CPU dry run of the mesh (``force_cpu``: 8 virtual devices, no chip):

    python examples/fedavg_lenet.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from sda_tpu.utils.backend import force_cpu

force_cpu(8)

import jax
import jax.numpy as jnp
import numpy as np
import optax

from sda_tpu.mesh import SimulatedPod, make_mesh
from sda_tpu.models import (
    FixedPointCodec,
    LeNet,
    LocalTrainer,
    param_count,
    pod_fedavg_round,
    ravel_pytree,
)
from sda_tpu.protocol import AdditiveSharing

M31 = (1 << 31) - 1
N_CLIENTS, ROUNDS, LOCAL_STEPS = 4, 3, 2

model = LeNet()
params = model.init(jax.random.PRNGKey(0), np.zeros((1, 28, 28, 1), np.float32))
print(f"LeNet: {param_count(params)} parameters")
gvec, unravel = ravel_pytree(params)

rng = np.random.default_rng(0)
xs = rng.normal(size=(N_CLIENTS, 16, 28, 28, 1)).astype(np.float32)
ys = rng.integers(0, 10, size=(N_CLIENTS, 16))


def loss_fn(p, batch):
    x, y = batch
    logits = model.apply(p, x)
    return optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()


trainer = LocalTrainer(loss_fn, optax.sgd(0.05))
pod = SimulatedPod(AdditiveSharing(share_count=8, modulus=M31),
                   mesh=make_mesh(4, 2))
codec = FixedPointCodec(M31, fractional_bits=16,
                        max_summands=N_CLIENTS, clip=4.0)


def global_loss(p):
    return float(np.mean([loss_fn(p, (xs[i], ys[i]))
                          for i in range(N_CLIENTS)]))


print(f"round 0: loss {global_loss(params):.4f}")
for r in range(1, ROUNDS + 1):
    client_vecs = []
    for i in range(N_CLIENTS):
        p = unravel(gvec)
        st = trainer.init_state(p)
        batches = (jnp.tile(xs[i][None], (LOCAL_STEPS, 1, 1, 1, 1)),
                   jnp.tile(ys[i][None], (LOCAL_STEPS, 1)))
        p, st, _ = trainer.fit(p, st, batches)
        client_vecs.append(ravel_pytree(p)[0])
    gvec = pod_fedavg_round(pod, codec, gvec, client_vecs,
                            jax.random.PRNGKey(r))
    params = unravel(gvec)
    print(f"round {r}: loss {global_loss(params):.4f} "
          f"(secure mesh round over {N_CLIENTS} encoded deltas)")
