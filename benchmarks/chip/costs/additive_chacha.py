"""What one additive-sharing round under ChaCha seed masks must move and
compute, from its shapes alone (the cost model ``additive_chacha_round``;
``costs.pod_round`` counts the packed round's ``[n, d/k]`` share rows and
on-core masks, which this round does not have).

Counts are of the algorithm, not of an implementation, read from the code
as ``costs.OPS_PER_ELEMENT`` was (+-20 %); uint32 ops an input element:

- ``canon`` 4: input -> canonical residue (``canon32``).
- ``chacha`` 202: a ChaCha20 block is 80 quarter rounds of 4 adds, 4 xors
  and 4 rotates (a rotate is 2 shifts and an or: 20 ops a quarter) plus 16
  adds of the initial state = 1616 ops for 16 words = 8 draws of 64 bits.
- ``reduce`` 25: (hi * 2^32 + lo) mod p done in 32-bit lanes as
  ``fastfield.uniform32`` does it -- two canons (8), a constant multiply
  modulo p (14), a modular add (3). The program asks for one ``jnp.mod``
  on emulated uint64 instead; that costs more and is not what is counted.
- ``mask_add`` 8: the mask add and the mask total (as ``OPS_PER_ELEMENT``).
- ``share_draw`` 145 a free share row, ``share_count`` - 1 rows: 64 random
  bits from threefry2x32 (20 rounds of add, rotate, xor = 100, five key
  injections = 20) and the same 25-op reduction.
- ``share_fold`` 3 a share row, ``share_count`` rows: the participant fold
  of each free row and of the masked input, one modular add each.

Once per column of the sum (``OPS_PER_SUMMED_ELEMENT``): the last row by
subtraction (``share_count`` - 1 adds and a subtract), the reveal
(``share_count`` adds), the unmask (a subtract), 3 ops each.
"""

from __future__ import annotations

OPS_PER_ELEMENT = {"canon": 4, "chacha": 202, "reduce": 25, "mask_add": 8}
OPS_PER_ELEMENT_AND_FREE_ROW = {"share_draw": 145}
OPS_PER_ELEMENT_AND_ROW = {"share_fold": 3}
OPS_PER_SUMMED_ELEMENT_AND_ROW = {"last_row_and_reveal": 6}
OPS_PER_SUMMED_ELEMENT = {"unmask": 3}

#: 64-bit draws one ChaCha20 block gives (16 words of 32 bits)
DRAWS_PER_BLOCK = 8


def round(participants: int, dim: int, input_itemsize: int,  # noqa: A001
          share_count: int, chips: int = 1) -> dict:
    """Bytes and ops of one round, per chip, with ``participants`` rows
    spread evenly over ``chips``.

    ``hbm_bytes`` is the floor: every input element is read from HBM once
    (``input_itemsize`` bytes: 4 for resident uint32 residues), the
    participant-summed share rows ``[n, d]`` and the mask total ``[d]``
    are written and read once as uint32, and the aggregate ``[d]`` is
    written as int64. Masks, the cipher's state and the share rows'
    randomness are made and used on the core and never need HBM.
    ``chacha_blocks``: blocks the masks of ``participants`` x ``dim``
    elements take, at 8 draws a block."""
    rows = participants // chips
    hbm_bytes = (rows * dim * input_itemsize
                 + 2 * 4 * share_count * dim
                 + 2 * 4 * dim
                 + 8 * dim)
    per_element = (sum(OPS_PER_ELEMENT.values())
                   + (share_count - 1) * sum(OPS_PER_ELEMENT_AND_FREE_ROW.values())
                   + share_count * sum(OPS_PER_ELEMENT_AND_ROW.values()))
    per_column = (share_count * sum(OPS_PER_SUMMED_ELEMENT_AND_ROW.values())
                  + sum(OPS_PER_SUMMED_ELEMENT.values()))
    return {"hbm_bytes": hbm_bytes,
            "vpu_ops": rows * dim * per_element + dim * per_column,
            "elements": rows * dim,
            "chacha_blocks": rows * -(-dim // DRAWS_PER_BLOCK)}
