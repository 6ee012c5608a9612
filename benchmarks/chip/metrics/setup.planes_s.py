"""Host seconds to build the input planes: the program's ``inputs.build``
span (``engine.build_inputs``: batch draws, latency draws, masks, the
copy to the device) less its ``inputs.replay_chain`` child.  Moves
``setup_s``."""
import phases


def read(run):
    build = phases.newest_span("inputs.build")
    replay = phases.newest_span("inputs.replay_chain")
    if build is None or replay is None \
            or not build.start_ns <= replay.start_ns <= build.end_ns:
        return None
    return build.seconds - replay.seconds
