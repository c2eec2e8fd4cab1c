"""`sda-bench` — the regression gate's front-end.

``sda-bench --check [records...]`` is the regression gate
(``sda_tpu.obs.regress``): compare the newest committed bench record
against its trailing window with noise-aware thresholds and exit
nonzero on a confirmed regression. Defaults to the repo's
``BENCH_r*.json`` trajectory. ``--advisory`` reports without gating
(the CI CPU rung), ``--json`` emits the verdict as one JSON line.

The flags mirror ``python -m sda_tpu.obs.regress`` exactly — one
implementation, two spellings. Speed on the chip is measured by
``python3 benchmarks/chip/run.py`` (``PERF.md``), not here.
"""

from __future__ import annotations

import sys
from typing import List, Optional

from ..obs import regress


def build_parser():
    parser = regress.build_parser()
    parser.prog = "sda-bench"
    parser.add_argument("--check", action="store_true",
                        help="run the regression gate (the only action)")
    return parser


def main(argv: Optional[List[str]] = None) -> int:
    return regress.run(build_parser().parse_args(argv))


if __name__ == "__main__":
    sys.exit(main())
