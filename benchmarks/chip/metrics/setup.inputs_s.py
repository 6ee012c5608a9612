"""Host seconds to build the deployment and its input planes: the
simulator (data, partition, straggler schedules, population store), the
chain replay and input planes of ``engine.build_inputs``, the initial
weights and the per-round slices.  Moves ``setup_s``."""


def read(run):
    return run.setup.get("inputs_s")
