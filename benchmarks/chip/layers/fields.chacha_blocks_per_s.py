"""Layer fields (field kernels), under ChaCha masking: ChaCha20 blocks the
device expands per second of cipher time -- the program's counters
``mesh.mask.chacha_blocks`` / ``mesh.mask.chacha_calls`` (blocks a
dispatch asks for, exact integers from shapes) over
``fields.mask_chacha_s_per_round``.

The counters are the process's, warm-up included; the quotient is a
round's blocks while every dispatch of the process has one shape, which
holds because ``drivers/pod_additive.py`` warms up at the cell's shape
(as ``feed.h2d_bytes_per_round`` and ``drivers/pod.py``). None where the
program has no such counter or no ``sda.mask.chacha`` scope."""

from reduce import scopes


def read(window):
    from sda_tpu.utils import metrics

    counters = metrics.counter_report("mesh.mask.")
    calls = counters.get("mesh.mask.chacha_calls")
    seconds = scopes.seconds_per_round(window, "sda.mask.chacha")
    if not calls or not seconds:
        return None
    return counters["mesh.mask.chacha_blocks"] / calls / seconds
