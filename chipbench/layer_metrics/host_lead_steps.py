"""``host_lead_steps``: how far the host runs in front of the device: the
median, over the traced slice's dispatches, of the optimizer steps
dispatched and not yet complete when a dispatch returned, itself included
(the ``ahead`` attr of the ``device_step`` spans, times the steps of a
dispatch: ``chipbench/step_spans.py``; the closing dispatch, whose call
the probe's fence holds, is left out).

A canary, not a goal: its ceiling is the traffic's and the runtime's, and
no change to the program can lift it. Where the epoch outlasts the
device's queue it reads the queue's cap (32 dispatches on this runtime),
and falls only when the host stops keeping up: near 1 the device waits
for every step and the next slowdown of the host is the run's. So it is
listed for ``resnet50-cifar.b512`` alone. An epoch of fewer steps than
the queue holds (dp4's 25, the decoder cells' 16) caps it at about half
the epoch, whatever the host does; there ``device_starved_ms`` says what
this cannot. None where the program stamps no step."""

import statistics

from chipbench import scopes, step_spans

NAME, UNIT, SOURCE = "host_lead_steps", "steps", "program_span"
LAYER = "run loop"
MOVES = "images_per_s_per_chip"


def read(run):
    piece = step_spans.of_run(run)
    if piece is None:
        return None
    lead = [step.ahead * step.steps for step in piece.steps
            if step.ahead is not None]
    if not lead:
        return None
    scopes.say(f"host lead: min {min(lead)} median "
               f"{statistics.median(lead)!r} max {max(lead)} steps over "
               f"{len(lead)} dispatches")
    return float(statistics.median(lead))
