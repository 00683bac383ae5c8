"""Process start to the first timed item (s): imports, data made from the
seed, loading or compiling every program, warm-up."""


def read(run):
    return run["setup_s"]
