"""Seconds in the program's `setup.init` span: `NetTrainer.init_model()`
(the net built, the parameters made, the optimizer state laid out), all
of it inside `setup_s`, since the harness brings the runtime up before.
Read from the set-up records the harness fetches after the window
(`benchmark/setup_spans.py`). Silent where the program keeps no such
records."""

from benchmark import setup_spans


def read(obs):
    return setup_spans.read(obs, "setup_init_s")
