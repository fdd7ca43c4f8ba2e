"""Process start to the first timed unit: imports, device init, loading
from the compile cache (or compiling), building state, one warm unit."""


def read(run):
    return run["setup_s"]
