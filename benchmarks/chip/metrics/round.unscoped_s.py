"""Device seconds a round of the window spends in ops under none of the
program's phase scopes: the simulated clock, masks, loss and carry
pass-through, and the copies XLA adds.  With the three phase metrics it
sums to the window's leaf-op seconds per round.  Moves
``samples_per_s``."""
import phases


def read(run):
    return phases.per_round_s(run, None)
