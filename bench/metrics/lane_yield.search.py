"""Expansion lanes that passed every filter over the lanes the match step
walked, summed over the window's queries, % (the program's counters)."""
from bench import program_spans


def read(run):
    walked = program_spans.counter(run, "lanes_processed")
    useful = program_spans.counter(run, "lanes_useful")
    if walked is None or not sum(walked):
        return None
    return 100.0 * sum(useful) / sum(walked)
