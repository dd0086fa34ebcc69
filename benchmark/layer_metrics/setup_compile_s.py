"""Seconds JAX spent compiling or loading compiled programs from the
persistent cache inside the program's set-up spans (their counters
`compile_s` + `cache_load_s`). Read from the set-up records the harness
fetches after the window (`benchmark/setup_spans.py`). Silent where the
program keeps no such records."""

from benchmark import setup_spans


def read(obs):
    return setup_spans.read(obs, "setup_compile_s")
