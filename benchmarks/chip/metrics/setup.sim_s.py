"""Host seconds to build the deployment: the program's ``sim.build`` span
around ``BHFLSimulator.__init__`` (data, partition, straggler schedules,
population store, chain, fault schedule).  Moves ``setup_s``."""
import phases


def read(run):
    s = phases.newest_span("sim.build")
    return s and s.seconds
