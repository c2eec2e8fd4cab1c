"""Layer fields (field kernels), under ChaCha masking: device seconds per
round in which an op under ``sda.mask`` ran and none under its four
children (``sda.mask.chacha`` / ``.reduce`` / ``.relayout`` / ``.fold``)
did -- the seed words, and on the kernel path, where the scan of the
mask expansion stands under ``sda.mask``, everything of that loop no
child names: the roots the compiler makes itself (the cipher's words
stacked in place, ``copy`` / ``copy-done``) and the loop's bookkeeping.
Instants, not ops (reduce/stages.py): the ``while`` that carries
``sda.mask`` encloses its body. Median over the traced rounds. None in
an untraced run and where no op carries a child scope."""

from reduce import stages


def read(window):
    return stages.remainder_per_round(
        window, stages.under("sda.mask"), stages.under(*stages.MASK_CHILDREN))
