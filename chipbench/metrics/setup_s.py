"""Seconds from process start to the start of the window: data, graph
build or load, engine, warm-up and any compilation."""


def read(run):
    return run.setup_s
