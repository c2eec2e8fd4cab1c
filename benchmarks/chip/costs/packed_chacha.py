"""What one packed-Shamir round under ChaCha seed masks must move and
compute, from its shapes alone (the cost model ``packed_chacha_round``).
``costs.pod_round`` and ``costs.fused_mask_share`` count the round whose
masks are drawn on the core inside the kernel; here the masks are cipher
streams expanded in front of the kernel, which then runs its mask-free
variant (``masked=False``: ``t`` draws a column and participant instead
of ``k + t``, no mask total written).

Counts are of the algorithm, not of an implementation, read from the code
as ``costs.OPS_PER_ELEMENT`` and ``costs.additive_chacha`` were (+-20 %).
``peaks.json`` has no int32 peak, so every floor taken from these is the
HBM bound alone: a share of it is a LOWER bound of the true roofline share.
"""

from __future__ import annotations

import costs
from costs import additive_chacha

#: uint32 ops an input element, in front of the kernel: residue canon, the
#: ChaCha20 block function, the draws' reduction, the masks' fold and the
#: inputs' fold (one modular add each)
OPS_PER_ELEMENT = {
    "canon": costs.OPS_PER_ELEMENT["canon"],
    "chacha": additive_chacha.OPS_PER_ELEMENT["chacha"],
    "reduce": additive_chacha.OPS_PER_ELEMENT["reduce"],
    "mask_fold": 3,
    "fold": 3,
}

#: uint32 ops an input element inside the mask-free kernel: what
#: ``costs.fused_mask_share`` counts less the mask's draw and its add
KERNEL_OPS_PER_ELEMENT = {
    name: ops for name, ops in costs.OPS_PER_ELEMENT.items()
    if name not in ("canon", "mask_draw", "mask_add")}


def kernel(participants: int, dim: int, secret_count: int, share_count: int,
           chips: int = 1) -> dict:
    """Bytes and ops of the mask-free kernel alone (``sda.mask_share`` in
    the trace), per chip, by the rule of ``costs.fused_mask_share``: the
    residues are read once as uint32 (since PR 25 the fold in front of the
    kernel does that read and the kernel takes the fold; the floor keeps
    the read so that the share compares with ``sda.mask_share_roofline``)
    and the combined share rows ``[n, d/k]`` are written. No mask total:
    the masks never enter the kernel."""
    rows = participants // chips
    columns = -(-dim // secret_count)
    return {"hbm_bytes": 4 * rows * dim + 4 * share_count * columns,
            "vpu_ops": (rows * dim * sum(KERNEL_OPS_PER_ELEMENT.values())
                        + dim * costs.OPS_PER_SUMMED_ELEMENT["share_matmul"])}


def round(participants: int, dim: int, input_itemsize: int,  # noqa: A001
          secret_count: int, share_count: int, chips: int = 1) -> dict:
    """Bytes and ops of one round, per chip, with ``participants`` rows
    spread evenly over ``chips``.

    ``hbm_bytes`` is the floor: every input element is read from HBM once
    (``input_itemsize`` bytes: 4 for resident uint32 residues), the
    combined share rows ``[n, d/k]`` and the masks' sum ``[d]`` are
    written and read once as uint32, and the aggregate ``[d]`` is written
    as int64. Masks, the cipher's state and the share polynomials'
    randomness are made and used on the core and never need HBM.
    ``chacha_blocks``: blocks the masks of ``participants`` x ``dim``
    elements take, at 8 draws a block."""
    rows = participants // chips
    columns = -(-dim // secret_count)
    hbm_bytes = (rows * dim * input_itemsize
                 + 2 * 4 * share_count * columns
                 + 2 * 4 * dim
                 + 8 * dim)
    per_element = (sum(OPS_PER_ELEMENT.values())
                   + sum(KERNEL_OPS_PER_ELEMENT.values()))
    return {"hbm_bytes": hbm_bytes,
            "vpu_ops": (rows * dim * per_element
                        + dim * sum(costs.OPS_PER_SUMMED_ELEMENT.values())),
            "elements": rows * dim,
            "chacha_blocks": rows * -(-dim // additive_chacha.DRAWS_PER_BLOCK)}
