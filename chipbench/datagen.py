"""Traffic generation: what every training set of a cell shares.

The training set itself is drawn by the generator the mix's ``dataset.kind``
names, ``datasets/<kind>.py::make(spec, seed)`` (``spec`` is the traffic
file's ``dataset`` block, ``seed`` the folded ``--seed``), a file the harness
finds as it finds every other (``run.py::load_cell``) and hands to the adapter
as ``ctx.dataset``. It returns a tuple of arrays whose first axis is the
example, so a training set of another task (token sequences) is a new file.
Here are the seed's fold and the sampler's arithmetic.
"""

from __future__ import annotations

#: numpy seeds and the program's ``TrainConfig.seed`` take any whole number;
#: the fold keeps a driver seed of a little over 2**31 inside 32 bits, which every consumer of the seed takes, before
#: the loader adds the epoch to it
SEED_MODULUS = 2**32


def fold_seed(seed: int) -> int:
    if seed < 0:
        raise ValueError(f"--seed must be a whole number >= 0, got {seed}")
    return int(seed) % SEED_MODULUS


def work_seed(traffic: dict, seed: int) -> int:
    """The folded seed that the training set and the weights are drawn from.
    As a rule ``--seed``. A mix that states ``fixed_work.seed`` draws both
    from that number in every run, and ``--seed`` draws only the order in
    which the set is fed (the sampler's permutation of each epoch) and what
    the step itself draws (a block-diffusion step's noise): the same set of
    sequences through the same routers in another order, so that a window of
    whole epochs holds the same work on every seed. It is for a cell whose
    work follows its seeded weights (a router that lands more or fewer pairs
    on the experts held here; PERF.md section 2)."""
    fixed = traffic.get("fixed_work")
    return fold_seed(seed if fixed is None else int(fixed["seed"]))


def tell_run_seed(reference, seed: int) -> None:
    """A reference that draws what the program's step draws from
    ``TrainConfig.seed`` (a block-diffusion step's noise) keeps the seed
    ``init_params`` was given; where the weights are not the run's seed's
    it is told the run's here (``run_seed``), after ``init_params``."""
    if hasattr(reference, "run_seed"):
        reference.run_seed(seed)


def real_examples_per_step(size: int, shards: int, per_shard_batch: int):
    """Unmasked examples of each step of one epoch, by the sampler's own
    arithmetic (DistributedSampler semantics: every shard holds
    ceil(size/shards) rows, wrap-padded duplicates are trained on and count;
    only the short last batch of the epoch is padded and masked)."""
    per_shard = -(-size // shards)
    steps = -(-per_shard // per_shard_batch)
    full = [per_shard_batch * shards] * (steps - 1)
    last = (per_shard - (steps - 1) * per_shard_batch) * shards
    return full + [last]
