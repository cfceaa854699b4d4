"""Seconds a user pays before serving: wall seconds from process start to
the end of the DB's build (corpus, graph, DB construction and prewarm),
plus the seconds JAX spent obtaining programs (tracing, lowering,
compiling or loading from the persistent cache) during the warm-up. The
warm-up's serving time is left out."""


def read(run):
    return run.setup_s
