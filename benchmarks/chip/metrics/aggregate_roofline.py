"""Edge and global aggregation's share of its roofline: the least time of
the window's warm HieAvg mixes (``work.aggregate_work`` over the model's
parameters, bytes-bound) over the device time of the whole aggregation
phase, the ops under the program's ``bhfl.edge_agg`` and
``bhfl.global_agg`` scopes, which ``round.aggregate_s`` reads too.

The count is of the model's ``param_shapes``, its federated parameters;
a model's frozen weights are never mixed and are not counted.  That
phase also initialises the histories and broadcasts each edge's model
back to its device slots.  The least count leaves both out on
purpose, so the share says how far the whole phase is from the traffic
that the mix itself needs.  ``None`` where the program names no phases.
Moves ``samples_per_s``."""
import phases
import work


def read(run):
    tel = phases.telemetry()
    t = tel and phases.per_round_s(run, (tel.EDGE_AGG, tel.GLOBAL_AGG))
    if not t:
        return None
    n = work.n_params(run.model.param_shapes(run.setting))
    least, _ = run.least_time(*work.aggregate_work(
        n, run.agg_participants, run.agg_outputs))
    return 100.0 * least / (t * run.rounds)
