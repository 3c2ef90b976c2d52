"""Chip benchmark of the elastic shell: one command runs one cell once.

    python3 -m bench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name: ``BENCHMARK.json`` lists the
cells, ``bench/configs/<config>.json`` holds a model configuration whose
``"arch"`` names its architecture, ``bench/arch/<arch>.py`` (weight
layout, plain reference and counts), ``bench/traffic/<mix>.json`` a
traffic mix (validated, generated and run by ``bench/drivers/<kind>.py``
for the ``kind`` it names, with token ids from
``bench/tokens/<dist>.py``), ``bench/cells/<cell>.json`` the cell's
correctness limits, and ``bench/metrics/<metric>.py`` the reader of one
per-layer metric (``<quantity>.py`` for a quantity split by the
end-to-end metric it moves).
"""

import sys
from pathlib import Path

# The system under test lives in ``<checkout>/src``.
_SRC = str(Path(__file__).resolve().parents[1] / "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)
