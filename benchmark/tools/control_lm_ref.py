#!/usr/bin/env python3
"""``control_lm.py`` for a cell whose driver is ``fit_tokens_ref``: the
float8 control of the comparison at the cell's own size, on the chip, with
the plain reference the configuration's file names.

    python3 benchmark/tools/control_lm_ref.py --workload <cell> --seeds 1 2 3

``control_lm.py`` binds ``reference/laguna.py`` by import, as the driver
``fit_tokens`` does; this binds the configuration's own for the process
(``drivers/fit_tokens_ref.bind``) and runs it unchanged. Run by hand when a
limit is set or checked; the benchmark's own runs do not run it.
"""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark import harness                           # noqa: E402
from benchmark.drivers import fit_tokens_ref            # noqa: E402
from benchmark.tools import control_lm                  # noqa: E402


def main(argv=None):
    args = list(sys.argv[1:] if argv is None else argv)
    cell = harness.Cell(args[args.index('--workload') + 1])
    control_lm.laguna = fit_tokens_ref.bind(cell.config)
    return control_lm.main(args)


if __name__ == '__main__':
    sys.exit(main())
