"""Device-idle time inside the program's ``block`` spans (a dispatch and
the host work before the next one), per block, ms."""
from bench import program_spans


def read(run):
    return program_spans.block_idle_ms(run)
