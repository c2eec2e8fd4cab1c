#!/usr/bin/env bash
# The rehearsals that cost no chip time (on-chip-measurement guide, section 2).
# Run from anywhere, before a chip call:  bash benchmarks/chip/rehearse.sh
#
#  1. every cell of BENCHMARK.json end to end at toy size on the CPU, kernels
#     interpreted, a four-chip cell on four virtual devices, untraced and traced;
#  2. the benchmark's own tests (trace reduction, costs, discovery, contract);
#  3. every pod cell's round compiled at its real size for a described v5e:2x2.
#
# Nothing here is a measurement: a rehearsal line says "rehearsal": true and
# carries no metric, and a compile that passes is not a chip run.
set -euo pipefail
cd "$(dirname "$0")/../.."
export JAX_PLATFORMS=cpu

cells=$(python3 -c "
import json
for w in json.load(open('BENCHMARK.json'))['workloads']:
    print(w['name'], w['chips'])")
while read -r cell chips; do
  for trace in 0 1; do
    echo "== rehearse $cell on $chips virtual device(s), trace $trace"
    XLA_FLAGS="--xla_force_host_platform_device_count=$chips" \
      python3 benchmarks/chip/run.py --workload "$cell" --seed 1 --seconds 1 \
      --trace "$trace" --rehearsal 2>/dev/null | tail -n 1
  done
done <<<"$cells"

echo "== the benchmark's own tests"
python3 -m pytest benchmarks/chip/tests -q -p no:cacheprovider

echo "== real sizes compiled for a described v5e:2x2"
python3 benchmarks/chip/compile_check.py 2>/dev/null
