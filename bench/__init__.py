"""The chip benchmark of the compiled CNN serving path.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json``. Everything one
configuration, traffic mix or metric needs sits in a file of its own
here, found by the name ``BENCHMARK.json`` gives it:

  configs/<config>.json      sizes, precision, placement, limits
  traffic/<traffic>.json     parameters, and the driver they name
  drivers/<driver>.py        the load generator over the timed path
  systems/<system>.py        the program under test, built for a cell
  references/<ref>.py        the plain float32 reference
  metrics/<metric>.py        one reader per metric

The shared yardstick: ``counts`` (operations and bytes from published
shapes), ``peaks`` (the chip table), ``roofline`` (a kernel's share),
``tracing`` (profiler trace to busy time, kernel time and idle gaps),
``stats`` (percentiles), ``weights`` (seeded weights and images),
``window`` (what a driver measured), ``correct`` (the comparison that
decides ``correct``), ``harness`` (one run of a cell).

Tools beside the command: ``readings.py`` (the program's and the
control's compared numbers over many seeds, which the limits are set
from) and ``sweep.py`` (the knee of a stream cell).
"""
