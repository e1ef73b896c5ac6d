"""engine_admit_ms.serve: milliseconds per serving-engine tick
in ``snn_engine.admit`` spans: popping each admitted request off the
queue, re-seeding its lane and setting up its slot.
The six ``engine_*_ms.serve`` phases add up to the mean
``snn_engine.step`` span (``harness/engine_spans.py``). Moves
p95_latency_ms: a request of n frames needs n ticks at K=1."""
from harness.engine_spans import phase_ms


def read(r):
    ms = phase_ms(r.trace)
    return None if ms is None else ms["admit"]
