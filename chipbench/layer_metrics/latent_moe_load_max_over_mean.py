"""``latent_moe_load_max_over_mean``: the fullest held expert of a step (over
the expert blocks) against the mean held expert, from the program's own
counters (the gauges ``model/expert_load_max`` and ``model/expert_load_mean``
of the traced run's run-end counters record, epoch means of per-step numbers
of what ``DroplessMoE`` sows as ``expert_load``). What the shipped
``expert_load_max_over_mean`` reads, under a name of this configuration's:
that metric lists its own cells. 1.0 is a router that favours no expert.
None where the program keeps no such counters."""

from chipbench import scopes

NAME, UNIT, SOURCE = ("latent_moe_load_max_over_mean", "ratio",
                      "program_counter")
LAYER = "models"
MOVES = "images_per_s_per_chip"


def read(run):
    gauges = (scopes.of_run(run)["counters"] or {}).get("gauges", {})
    top, mean = (gauges.get("model/expert_load_max"),
                 gauges.get("model/expert_load_mean"))
    if top is None or not mean:
        return None
    scopes.say(f"expert load: max {top!r} mean {mean!r} pairs a step; landed "
               f"{gauges.get('model/expert_load_sum')!r} a step, rows walked "
               f"{gauges.get('model/expert_rows_walked_sum')!r}")
    return top / mean
