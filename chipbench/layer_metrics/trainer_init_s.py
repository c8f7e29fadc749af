"""``trainer_init_s``: harness clock around building the ``Trainer`` (the
program's own state initialisation and the install of the seeded weights
included)."""

NAME, UNIT, SOURCE = "trainer_init_s", "s", "host_clock"
LAYER = "entry points"
MOVES = "setup_s"


def read(run):
    return run.record.get("trainer_init_s")
