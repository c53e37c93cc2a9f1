"""``tools/kernel_schedule.py``'s two readers on a made-up dump: the bundles a
grid step walks (a loop's body times its trips, hoisted outer work inside a
body counted with it) and the grid's steps (which say how many batch rows a
grid step of the delta rules holds), the units' mean use a window or a
stretch, and the stretches between control marks (a ``pl.when`` body's end)."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tools"))

import kernel_schedule as ks  # noqa: E402


def bundles(marks):
    """A ``final_bundles`` text: one line a bundle from (mark, depth, trailer)."""
    lines = ["= control target key start", "LB: loop body", "= control target key end", ""]
    for n, (mark, depth, rest) in enumerate(marks):
        head = f"{n:#x}" if n else "0"
        lines.append(f"  {head:>5s} {mark or '  '}: {'>' * depth} {{ %v{n} = vadd.f32 %a, %b{rest} }}")
    return "\n".join(lines)


def exit_test(trips):
    return f"  ;;  %p1 = scmp.ge.s32.totalorder %s9_s1, {trips} /* loop exit test */"


@pytest.mark.parametrize("hoisted", [0, 3], ids=["plain", "outer-work-inside-the-body"])
def test_a_grid_step_is_the_loops_bodies_times_their_trips(hoisted):
    first = [("LB", 2, "")] + [("", 2, "")] * 4 + [("", 1, "")] * hoisted + [("", 2, exit_test(2))]
    second = [("LB", 2, "")] + [("", 2, "")] * 2 + [("", 2, exit_test(8))]
    text = bundles([("", 0, "")] * 2 + [("LB", 1, "")] + [("", 1, "")] * 9 + first + second
                   + [("", 1, exit_test(1026))] * 1 + [("", 0, "")])
    step, inner, total, grid = ks.loops(text)
    assert grid == 1024                 # the grid loop's exit test less the pipeline's two
    assert inner == [(6 + hoisted, 2), (4, 8)]
    assert total == 14 + 6 + hoisted + 4
    assert step == 14 + 2 * (6 + hoisted) + 8 * 4


@pytest.mark.parametrize("family,trips,held", [("kda", 1026, 1), ("kda", 514, 2), ("gdn", 258, 2)],
                         ids=["a-row-a-step", "kda-two-rows", "gdn-two-rows"])
def test_a_kernel_without_inner_loops_is_its_text_and_the_grid_says_its_rows(family, trips, held):
    """One long block a grid step; the (row, head, block) triples of the cell's
    shape over the grid's steps are the batch rows a grid step holds."""
    step, inner, total, grid = ks.loops(bundles([("", 0, "")] + [("LB", 1, "")] + [("", 1, "")] * 20
                                                + [("", 1, exit_test(trips))]))
    assert (step, inner, total, grid) == (23, [], 23, trips - 2)
    assert ks.TRIPLES[family] // grid == held


def test_unit_use_is_a_share_of_each_units_capacity():
    rows = ["== CAPACTIY:", "MXU, XLU, VALU, EUP, VLOAD, VLOAD:FILL, VSTORE, VSTORE:SPILL, SALU",
            "    4     3     4     1     3     3     1     1     2", "== UTILIZATION:"]
    rows += ["4 0 2 0 0 0 1 1 0"] * 2 + ["0 3 2 1 3 0 0 0 2"] * 2 + ["2 0 0 0 0 0 0 0 0"]
    windows = ks.unit_use("\n".join(rows), 4)
    assert [(first, n) for first, n, _ in windows] == [(0, 4), (4, 1)]
    use = windows[0][2]
    assert use["MXU"] == 0.5 and use["XLU"] == 0.5 and use["VALU"] == 0.5 and use["EUP"] == 0.5
    assert use["VSTORE"] == 0.5 and use["SPILL"] == 0.5 and use["SALU"] == 0.5
    assert windows[1][2]["MXU"] == 0.5 and windows[1][2]["VALU"] == 0.0
    # by stretch: from each given first bundle to the next
    stretches = ks.unit_use("\n".join(rows), firsts=[0, 2])
    assert [(first, n) for first, n, _ in stretches] == [(0, 2), (2, 3)]
    assert stretches[0][2]["MXU"] == 1.0 and stretches[1][2]["XLU"] == 2 / 3


def test_a_predicated_region_ends_at_its_fallthrough_mark():
    """A ``pl.when`` body is a predicated region: the flash forward's text is
    prologue, init, the tile with no mask, the tile under the mask, finish."""
    text = bundles([("", 0, "")] * 2 + [("LB", 1, "")] + [("", 1, "")] * 3 + [("PF", 1, "")]
                   + [("", 1, "")] * 7 + [("PF", 1, "")] + [("", 1, "")] * 9 + [("PF", 1, "")]
                   + [("", 1, exit_test(2050))])
    assert ks.regions(text) == [0, 2, 6, 14, 24]
    # the parent process's labels are the families' own
    assert ks.LABELS == {family: tuple(label for label, _, _ in kernels)
                         for family, kernels in ks.families().items()}
