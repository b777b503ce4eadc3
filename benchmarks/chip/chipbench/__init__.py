"""On-chip benchmark harness: one process runs one cell once.

Everything that belongs to one configuration, traffic mix or metric lives
in a file of its own beside this package, found by the name that
``BENCHMARK.json`` gives it:

    configs/<config>.json        the configuration as it is run
    traffic/<traffic>.json       the parameters of one traffic mix
    metrics/<stem>.py            the reader of every metric named <stem>[.x]
    references/<family>.py       the plain reference of a model family
    work/<name>.py               operation and byte counts from shapes

The harness itself (this package) holds what every cell shares: the
traffic generator, the pump that drives the engine, the trace reduction
and the result line.
"""
