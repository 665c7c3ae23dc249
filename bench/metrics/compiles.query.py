"""Backend compiles per query in the window, as the program counts them."""
from bench import program_spans


def read(run):
    counts = program_spans.counter(run, "compiles")
    return None if counts is None else sum(counts) / len(counts)
