"""``latent_moe_rows_walked_over_landed``: the rows the routed experts'
buffers had in a step (over the expert blocks: the rung of
``models/moe.py``'s ladder each call took) against the (token, choice) pairs
that landed on the held experts, from the program's own counters (the gauges
``model/expert_rows_walked_sum`` and ``model/expert_load_sum`` of the traced
run's run-end counters record, epoch means of per-step sums of what
``DroplessMoE`` sows as ``expert_rows_walked`` and ``expert_load``). What
the shipped ``moe_rows_walked_over_landed`` reads, under a name of this
configuration's: that metric lists its own cells. 1.0 is a buffer with no
row to spare; a block that landed more than the short rung holds walks the
long one, and the ratio says so. None where the program keeps no such
counter."""

from chipbench import scopes

NAME, UNIT, SOURCE = ("latent_moe_rows_walked_over_landed", "ratio",
                      "program_counter")
LAYER = "models"
MOVES = "images_per_s_per_chip"


def read(run):
    gauges = (scopes.of_run(run)["counters"] or {}).get("gauges", {})
    walked, landed = (gauges.get("model/expert_rows_walked_sum"),
                      gauges.get("model/expert_load_sum"))
    if walked is None or not landed:
        return None
    scopes.say(f"expert rows: walked {walked!r} landed {landed!r} a step; "
               f"longest buffer {gauges.get('model/expert_rows_walked_max')!r}")
    return walked / landed
