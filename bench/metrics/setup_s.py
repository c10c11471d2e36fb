"""Process start to the window's first dispatch (host clock): imports,
backend start, program build, state made from the seed, the check steps
(which warm up the step and, on a cold cache, compile it).  The readouts
the comparison takes during the check steps are not counted."""


def read(run):
    return run.setup_s
