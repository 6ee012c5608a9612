"""Device seconds a round of the window spends in local training: the ops
under the program's ``bhfl.train`` scope (the batch gather, every SGD
step's forward, backward and update).  Moves ``samples_per_s``."""
import phases


def read(run):
    tel = phases.telemetry()
    return tel and phases.per_round_s(run, (tel.TRAIN,))
