"""Seconds from process start to the window's start: corpus, graph, DB
construction and prewarm, warm-up."""


def read(run):
    return run.setup_s
