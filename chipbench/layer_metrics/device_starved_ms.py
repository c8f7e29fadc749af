"""``device_starved_ms``: time the device had no step to run, as the host
sees it: the gaps between consecutive ``device_step`` spans of the traced
slice (``chipbench/step_spans.py``: one span's completion to the next
one's start, which is its dispatch's return where the queue was empty; up
to the start of the closing call for the last), summed and divided by the
slice's optimizer steps. The host's own count of what the device trace's
idle share reads from the device; an epoch boundary's drain and refill is
in it, the opening of the slice and the fence that closes it are not. None
where the program stamps no step."""

from chipbench import scopes, step_spans

NAME, UNIT, SOURCE = "device_starved_ms", "ms", "program_span"
LAYER = "run loop"
MOVES = "images_per_s_per_chip"


def read(run):
    piece = step_spans.of_run(run)
    steps = run.record.get("steps")
    if piece is None or not steps:
        return None
    gaps = step_spans.gaps_s(piece)
    largest = sorted(gaps, reverse=True)[:3]
    scopes.say(f"device starved: {sum(gaps)!r} s in {len(gaps)} gaps over "
               f"{steps} steps, the largest {largest!r}")
    return sum(gaps) * 1e3 / steps
