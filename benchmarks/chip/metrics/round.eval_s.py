"""Device seconds a round of the window spends on the test-set eval: the
ops under the program's ``bhfl.eval`` scope.  Moves ``samples_per_s``."""
import phases


def read(run):
    tel = phases.telemetry()
    return tel and phases.per_round_s(run, (tel.EVAL,))
