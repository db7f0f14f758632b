"""Controller and scheduler: requests per successful EXEC in the window,
from the controller's action records."""


def read(run):
    execs = run.execs()
    if not execs:
        return None
    return sum(a.batch_size for a in execs) / len(execs)
