"""Device seconds a round of the window spends aggregating: the ops under
the program's ``bhfl.edge_agg`` and ``bhfl.global_agg`` scopes (history
init and update, the cold and warm HieAvg mixes, the broadcast back to
the device slots).  Moves ``samples_per_s``."""
import phases


def read(run):
    tel = phases.telemetry()
    return tel and phases.per_round_s(run, (tel.EDGE_AGG, tel.GLOBAL_AGG))
