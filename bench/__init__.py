"""The chip benchmark of the FLEXIS miner: see ``run.py`` and PERF.md."""
