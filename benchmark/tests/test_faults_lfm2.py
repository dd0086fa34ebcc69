"""The LFM2 cell's limits against what they were set against on the
chip, at the dry run's size on the CPU: the control (float8 operands) or
one broken mechanism of `benchmark/reference/lfm2.py` put in the
program's place reads over at least one limit, and the program itself
under all of them. One rehearsal of the program and of the sound
reference serves every case (`tools/read_limits_tokens.py` reads the
chip the same way)."""

import os
from types import SimpleNamespace

import pytest

from benchmark import run as bench
from benchmark.reference import lfm2

CELL = "lfm2_24b_a2b.train_seq32k"
SEED = 2147483999


@pytest.fixture(scope="module")
def rehearsal():
    cell = bench.load_cell(CELL)
    driver = bench.load_module(os.path.join(
        bench.HERE, "drivers", "train_tokens.py"))
    overrides = dict(cell.cfg["overrides"], **cell.cfg["dry_run_overrides"])
    ref = driver.make_reference(cell.cfg, overrides)
    prep = driver.prepare(cell.cfg, cell.traffic, SEED, overrides, ref)
    program, batches = prep.readings, prep.batches
    driver.free(prep)
    sound = driver.reference_readings(ref, cell.cfg, cell.traffic, SEED,
                                      batches)

    def over_a_limit(readings):
        numbers, _ = driver.compare(readings, sound)
        return sorted(n for n, limit in cell.limits.items()
                      if n in numbers and numbers[n] > limit)

    def variant(name):
        return driver.reference_readings(
            driver.make_reference(cell.cfg, overrides, name), cell.cfg,
            cell.traffic, SEED, batches)

    return SimpleNamespace(program=program, over_a_limit=over_a_limit,
                           variant=variant)


def test_the_program_reads_under_every_limit(rehearsal):
    assert rehearsal.over_a_limit(rehearsal.program) == []


@pytest.mark.parametrize("variant", ["float8_e4m3fn"] + list(lfm2.FAULTS))
def test_a_broken_reference_in_the_programs_place_is_not_correct(
        rehearsal, variant):
    assert rehearsal.over_a_limit(rehearsal.variant(variant)), variant
