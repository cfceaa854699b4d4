"""Mean recall@k, against the float64 reference's optimal diverse set, of
the sample of the window's answers drawn from the seed."""


def read(run):
    return run.recall
