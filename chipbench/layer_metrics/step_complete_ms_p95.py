"""``step_complete_ms_p95``: the cadence as the device keeps it: the 95th
percentile of the interval between consecutive step completions of the
traced slice (the ends of its ``device_step`` spans,
``chipbench/step_spans.py``; the closing dispatch's, stamped after the
probe's fence, is left out), each over the optimizer steps of the dispatch
that completed. Where the end-to-end ``step_ms_p95`` reads the
host's dispatches, which in a short epoch never meet a full queue, this
reads what the device finished, epoch boundaries included. None where the
program stamps no step, or the slice has fewer than 20 intervals."""

import statistics

from chipbench import scopes, step_spans

NAME, UNIT, SOURCE = "step_complete_ms_p95", "ms", "program_span"
LAYER = "run loop"
MOVES = "images_per_s_per_chip"
INTERVALS = 20  # as the end-to-end tail: fewer have no 95th percentile


def read(run):
    piece = step_spans.of_run(run)
    if piece is None:
        return None
    intervals = [(b.end - a.end) * 1e3 / b.steps
                 for a, b in zip(piece.steps, piece.steps[1:])]
    if len(intervals) < INTERVALS:
        return None
    scopes.say(f"completion intervals: n={len(intervals)} median_ms="
               f"{statistics.median(intervals)!r} max_ms={max(intervals)!r}")
    # the 19th of 20 cuts, ends included: numpy's default percentile, the
    # one the end-to-end tail takes (``run.py::percentile``)
    return statistics.quantiles(intervals, n=20, method="inclusive")[-1]
