"""Tier-1's share of the benchmark's own tests (``benchmarks/tests/``): toy-size
CPU rehearsals of the training adapter through the harness, so that a change
which breaks the O2 step for the benchmark is found here and not on the chip.
The tests themselves live with the benchmark."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.tests.test_harness import (  # noqa: E402,F401
    test_every_cell_reports_the_names_it_reported_before_the_fold,
    test_manifest_keeps_to_the_contract,
    test_new_files_and_entries_alone_add_a_cell,
)
from benchmarks.tests.test_rehearsal import (  # noqa: E402,F401
    here,
    test_a_step_that_leaves_out_part_of_the_batch_is_not_correct,
    test_a_step_that_returns_its_state_unchanged_is_not_correct,
    test_traced_train_rehearsal_reports_layer_metrics,
    test_train_rehearsal_is_correct_and_reports_the_contract_keys,
)
