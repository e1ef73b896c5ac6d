"""engine_tick_ms.serve: mean host-clock time of one
``SNNServeEngine.step`` call in the measured window (serving engine,
``serve/snn_engine.py``). Moves p95_latency_ms: a request of n frames
needs n ticks at K=1."""


def read(r):
    n = r.counters.get("ticks", 0)
    if not n:
        return None
    return r.counters["step_s_sum"] / n * 1e3
