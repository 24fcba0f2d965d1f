"""Trainer API. The program's share of set-up: the sum of every stage it books
from the process' start to the window - import, build, trace, lower,
compile, cache_load, place, first_run. `setup_s` less this is the
benchmark's own: reaching the chip, the weights from the seed, the
reference, the ramp. program_setup_seconds_total{stage=*}
(paddle_tpu/coldstart.py), the process' cumulative counters at the end of
the run. Moves setup_s."""
from benchmark import setup_stages


def read(facts):
    return setup_stages.setup_program_s()
