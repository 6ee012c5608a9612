"""Host seconds to replay the consensus chain: the program's
``inputs.replay_chain`` span inside ``engine.build_inputs``.  Moves
``setup_s``."""
import phases


def read(run):
    s = phases.newest_span("inputs.replay_chain")
    return s and s.seconds
