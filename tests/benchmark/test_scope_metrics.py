"""Tier-1's share of the block-level readers' tests (``benchmarks/tests/
test_scope_metrics.py``): a span renamed in the program, or a change to what
``apex_tpu.prof.scopes`` hands the readers, is found here and not on the
chip."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.tests.test_scope_metrics import (  # noqa: E402,F401
    test_a_program_without_the_rollup_reads_as_nothing,
    test_manifest_lists_each_block_metric_in_the_cells_that_hold_its_spans,
    test_reader_returns_none_where_no_operation_matches,
    test_reader_returns_none_without_a_trace_or_a_table,
    test_reader_sums_its_spans_on_a_hand_made_run,
    test_the_books_close_on_the_hand_made_run,
)
