"""How many functions JAX traced inside the program's set-up spans
(their counter `traces`): a count that repeats exactly from run to run
of one tree, so a change to what set-up traces shows without noise.
Read from the set-up records the harness fetches after the window
(`benchmark/setup_spans.py`). Silent where the program keeps no such
records."""

from benchmark import setup_spans


def read(obs):
    return setup_spans.read(obs, "setup_traces")
