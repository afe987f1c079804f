"""Process start to the window's opening: data generation, table, session,
first upload, warm-up and, in a run that compiles, compilation."""


def read(run):
    return run.setup_s
