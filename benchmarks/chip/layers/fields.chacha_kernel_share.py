"""Layer fields (field kernels), under ChaCha masking: the share of the
ChaCha20 blocks a dispatch asks of the mesh that the on-core cipher
(``fields/chacha_kernel.py``, the Pallas kernel ``sda_chacha_mask_fold``)
is asked for -- the program's counters ``mesh.mask.chacha_kernel_blocks``
/ ``mesh.mask.chacha_blocks``, both settled in ``SimulatedPod._build``
from static shapes. 1.0 where a round is lowered for a TPU over a uint32
field, 0 where the XLA block function runs.

The process's, warm-up included. None on a program without either
counter: a program from before the kernel."""


def read(window):
    from sda_tpu.utils import metrics

    counters = metrics.counter_report("mesh.mask.")
    kernel = counters.get("mesh.mask.chacha_kernel_blocks")
    blocks = counters.get("mesh.mask.chacha_blocks")
    if kernel is None or not blocks:
        return None
    return kernel / blocks
