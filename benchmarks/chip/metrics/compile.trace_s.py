"""Host seconds to trace the window's one-round program
(``engine.run_engine_chunk``) to a jaxpr and lower it to MLIR, from the
program's compile counters.  Part of ``setup.compile_s`` that a persistent
cache cannot skip: only the backend compile is cached.  Moves
``setup_s``."""
import phases


def read(run):
    tel = phases.telemetry()
    if tel is None:
        return None
    counts = tel.counters()
    traced = counts.get("trace_s", {}).get("run_engine_chunk")
    lowered = counts.get("lower_s", {}).get("jit(run_engine_chunk)")
    if traced is None or lowered is None:
        return None
    return traced + lowered
