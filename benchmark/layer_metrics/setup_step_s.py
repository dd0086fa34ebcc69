"""Seconds in the program's `setup.step` spans: each train-step shape's
first dispatch, which traces, lowers and compiles the step or loads it
from the compile cache. Read from the set-up records the harness fetches
after the window (`benchmark/setup_spans.py`). Silent where the program
keeps no such records."""

from benchmark import setup_spans


def read(obs):
    return setup_spans.read(obs, "setup_step_s")
