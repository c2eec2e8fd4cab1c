"""What one round must move and compute, from its shapes alone.

The op/byte model of ``benchmarks/ROOFLINE.md`` as functions, kept with
the benchmark so that no PR that claims a gain can change the yardstick.
Counts are of the algorithm, not of an implementation: a kernel that
moves more bytes than :func:`pod_round` counts is further from the
floor, not differently scored.

``peaks.json`` holds the chip's peaks by ``device_kind``, each with its
source; a device that is not in it is an error, not a default.
"""

from __future__ import annotations

import json
from pathlib import Path

#: uint32 VPU ops per input element and stage, from reading the kernels
#: (benchmarks/ROOFLINE.md, +-20 %): residue canon, mask draw, mask add
#: and mask total, share randomness (t/k draws per element), and the
#: participant fold. They are paid once per element.
OPS_PER_ELEMENT = {"canon": 4, "mask_draw": 35, "mask_add": 8,
                   "share_randomness": 47, "fold": 6}

#: per *column of the sum* (once per round, participants already folded,
#: because sharing is linear): the share contraction, n*(k+t)/k limb
#: multiply-adds of ~12 ops each, and reconstruction plus unmasking.
OPS_PER_SUMMED_ELEMENT = {"share_matmul": 250, "reconstruct_unmask": 3}


def peaks(device_kind: str) -> dict:
    table = json.loads((Path(__file__).parent / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}: add a "
                       f"sourced row to costs/peaks.json")
    return table[device_kind]


def pod_round(participants: int, dim: int, input_itemsize: int,
              secret_count: int, share_count: int, chips: int = 1) -> dict:
    """Bytes and ops of one packed-Shamir pod round with full masking,
    per chip, with ``participants`` rows spread evenly over ``chips``.

    ``hbm_bytes`` is the floor: every input element is read from HBM once
    (``input_itemsize`` bytes: 4 for resident uint32 residues, 8 for the
    int64 a host feeds), the combined share rows ``[n, d/k]`` and the
    mask totals ``[d]`` are written and read once as uint32, and the
    aggregate ``[d]`` is written as int64. Masks and share randomness are
    drawn on the core and never touch HBM.
    """
    rows = participants // chips
    columns = -(-dim // secret_count)
    hbm_bytes = (rows * dim * input_itemsize
                 + 2 * 4 * share_count * columns
                 + 2 * 4 * dim
                 + 8 * dim)
    vpu_ops = (rows * dim * sum(OPS_PER_ELEMENT.values())
               + dim * sum(OPS_PER_SUMMED_ELEMENT.values()))
    return {"hbm_bytes": hbm_bytes, "vpu_ops": vpu_ops,
            "elements": rows * dim}


def fused_mask_share(participants: int, dim: int, secret_count: int,
                     share_count: int, chips: int = 1) -> dict:
    """Bytes and ops of the fused kernel alone (``sda.mask_share`` in the
    trace: mask, share and participant fold in one pass), per chip: it
    reads the residues once as uint32 and writes the combined share rows
    ``[n, d/k]`` and the mask totals ``[d]``; everything per element of
    :data:`OPS_PER_ELEMENT` but the residue pass, and the share
    contraction once per column of the sum."""
    rows = participants // chips
    columns = -(-dim // secret_count)
    per_element = sum(OPS_PER_ELEMENT.values()) - OPS_PER_ELEMENT["canon"]
    return {"hbm_bytes": 4 * rows * dim + 4 * share_count * columns + 4 * dim,
            "vpu_ops": (rows * dim * per_element
                        + dim * OPS_PER_SUMMED_ELEMENT["share_matmul"])}


def floor_seconds(cost: dict, device_kind: str) -> float:
    """The least time the chip could take for ``cost``: the larger of
    bytes over peak bytes/s and ops over peak ops/s. Where the table has
    no compute peak (the v5e's int32 VPU rate is unpublished), it is the
    HBM bound alone, and a share of it is a lower bound of the true
    roofline share."""
    row = peaks(device_kind)
    seconds = cost["hbm_bytes"] / row["hbm_bytes_per_s"]
    if row.get("int32_ops_per_s"):
        seconds = max(seconds, cost["vpu_ops"] / row["int32_ops_per_s"])
    return seconds
