"""Host seconds to lower and compile the window's one-round program ahead
of time; from the persistent cache after a checkout's first run.  Moves
``setup_s``."""


def read(run):
    return run.setup.get("compile_s")
