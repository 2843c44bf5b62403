"""Tier 1 runs the rule that decides every serving run's `correct`.

`chipbench/kinds/serving.py schedule_kept` says whether an open loop's
sender kept its schedule; its pure cases (numpy only) live with the
benchmark in chipbench/tests/test_schedule.py, which tier 1 does not
collect.  They are collected here by name.  The four rehearsals of that
file start whole benchmark runs in subprocesses and stay where they are.
"""
from chipbench.tests.test_schedule import (  # noqa: F401
    CASES, test_schedule_kept,
    test_the_rule_reads_nothing_the_program_sets,
    test_the_stretches_say_where_how_many_and_how_late)
