"""Tier-1's share of the kernels layer's readers (``benchmarks/tests/
test_kernel_metrics.py``): a change to a kernel's name, or to what the
trace reduction hands the readers, is found here and not on the chip."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.tests.test_kernel_metrics import (  # noqa: E402,F401
    test_a_program_that_names_nothing_reads_as_nothing,
    test_every_train_cell_reports_the_kernel_metrics,
    test_readers_on_names_as_the_chip_spells_them,
    test_recorded_named_trace_from_the_chip,
    test_required_work_by_hand,
)
from benchmarks.tests.test_trace_reduce import (  # noqa: E402,F401
    test_an_idle_gap_is_named_by_the_innermost_host_event,
)
