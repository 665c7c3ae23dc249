"""Share of the window's device-idle time inside ``mine()`` that none of
the program's spans below ``mine`` names, %."""
from bench import program_spans


def read(run):
    return program_spans.idle_unspanned(run)
