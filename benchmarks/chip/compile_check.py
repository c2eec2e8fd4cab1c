"""Compile every ``pod`` cell's round at its real size for a TPU v5e
that is described, not attached (rehearse.sh; on-chip-measurement guide,
section 2). Nothing runs: this finds what the chip's compiler refuses --
a program that does not fit HBM, a kernel Mosaic rejects, a sharding that
does not partition -- before a chip call is spent on it. It prints the
bytes per device and whether the kernel and the collectives are in the
compiled program. A compile that passes is not a chip run.
"""

from __future__ import annotations

import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp
os.environ["JAX_PLATFORMS"] = "cpu"

import harness  # noqa: E402

sys.path.insert(0, str(harness.ROOT))
import sda_tpu  # noqa: E402,F401  (x64)
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec  # noqa: E402

TOPOLOGY = "v5e:2x2"


def main() -> int:
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    topology = topologies.get_topology_desc(platform="tpu", topology_name=TOPOLOGY)
    for entry in spec["workloads"]:
        cell = harness.load_cell(harness.ROOT, entry["name"])
        if cell.config["driver"] != "pod":
            continue
        pod_driver = harness.load_module(cell.home, "drivers", "pod")
        pod = pod_driver.build_pod(cell.config, topology.devices[:cell.chips])
        padded = pod.padded_shape(cell.traffic["participants"], cell.traffic["dim"])
        dtype = jnp.int64 if cell.traffic["input"] == "host" else jnp.uint32
        inputs = jax.ShapeDtypeStruct(
            padded, dtype, sharding=NamedSharding(pod.mesh, PartitionSpec("p", "d")))
        key = jax.ShapeDtypeStruct(
            (2,), jnp.uint32, sharding=NamedSharding(pod.mesh, PartitionSpec()))
        start = time.perf_counter()
        compiled = pod.aggregate_fn(*padded).lower(inputs, key).compile()
        memory = compiled.memory_analysis()
        text = compiled.as_text()
        print(json.dumps({
            "cell": cell.name, "topology": TOPOLOGY, "chips": cell.chips,
            "mesh": list(pod.mesh.devices.shape), "padded": list(padded),
            "dtype": jnp.dtype(dtype).name,
            "compile_s": round(time.perf_counter() - start, 1),
            "per_device_bytes": {
                "arguments": memory.argument_size_in_bytes,
                "temporaries": memory.temp_size_in_bytes,
                "outputs": memory.output_size_in_bytes},
            "kernel_in_program": "tpu_custom_call" in text,
            "collectives": sorted({op for op in (
                "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute") if op in text}),
            "compiled_only": True,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
