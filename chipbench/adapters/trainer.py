"""Adapter ``trainer``: a cell is the product's own loop, ``Trainer.run``.

The adapter builds a ``TrainConfig`` from the cell's two data files, constructs
the ``Trainer`` the way ``tpu_ddp/cli/train.py`` does, gives it the benchmark's
seeded training set (the generator the mix's ``dataset.kind`` names) and seeded
weights, hands it back its own step callable wrapped in a ``StepProbe``, and
calls ``Trainer.run``. It never iterates the loader and never calls the step
itself. What a batch holds, what the loss is and which optimizer steps it are
not known here: the probe copies batches as they are fed, and the record
carries the optimizer the files state (``record["optimizer"]``) for the
configuration's reference file to follow.

The probe is the only seam: every dispatch the ``Trainer`` makes goes through
it. It reads the first three steps for ``correct`` (set-up), opens the window
at a fence at the first dispatch after epoch 1, takes two clock reads per
dispatch, and at the end of the first epoch to finish after ``--seconds``
closes the window at a fence and ends the run through the ``Trainer``'s own
drain (the preemption flag). A window is whole epochs: the same work in every
run of a cell.

From ``tpu_ddp`` it takes ``TrainConfig``, ``Trainer`` and, in a traced run,
the telemetry's span stream. Nothing else. ``StepProbe`` takes nothing of the
``Trainer`` but the flag that ends its loop, so an adapter that drives a step
another way (a new file under ``adapters/``) stands the same probe in front of
its own step callable.
"""

from __future__ import annotations

import os
import shutil
import time

import numpy as np

from chipbench import datagen

#: steps of set-up whose inputs and outputs ``correct`` reads
CHECK_STEPS = 3
#: rows of every array of the training set that the ``Trainer`` is given as
#: its held-out set; no cell evaluates, so they are never read
HELD_OUT = 64
#: the traced slice: at most this many seconds and this many dispatches
TRACE_SECONDS = 3.0
TRACE_DISPATCHES = 300
#: a run that must never end by itself: the probe ends it
EPOCHS = 1_000_000
#: what a traced run switches on: the telemetry's spans and nothing that
#: adds its own per-step work (digests, memory samples)
TRACE_OVERLAY = {"telemetry_sinks": "jsonl", "data_digests": False,
                 "mem_sample_steps": 0, "telemetry_snapshot_steps": 0}
HOST_SPANS = ("data_wait", "h2d", "compiled_step", "device_sync",
              "epoch_metrics_fetch")


def _flatten(tree, prefix=()):
    out = {}
    for key, value in tree.items():
        if isinstance(value, dict):
            out.update(_flatten(value, prefix + (key,)))
        else:
            out[prefix + (key,)] = value
    return out


def _unflatten(flat):
    tree = {}
    for path, value in flat.items():
        node = tree
        for key in path[:-1]:
            node = node.setdefault(key, {})
        node[path[-1]] = value
    return tree


def install_weights(trainer, ref_params: dict, names: dict):
    """Put the benchmark's seeded weights where the ``Trainer`` made its own,
    leaf for leaf by name; shapes and the set of leaves must agree exactly."""
    import jax

    old = _flatten(dict(trainer.state.params))
    new = {names[k]: v for k, v in ref_params.items()}
    if set(old) != set(new):
        raise ValueError(
            "the reference's leaves are not the program's: "
            f"{sorted(set(old) ^ set(new))[:6]}")
    for path, leaf in old.items():
        if leaf.shape != new[path].shape or leaf.dtype != new[path].dtype:
            raise ValueError(
                f"{'/'.join(path)}: program {leaf.shape} {leaf.dtype}, "
                f"reference {new[path].shape} {new[path].dtype}")
        if leaf.committed:  # keep the layout the program chose
            new[path] = jax.device_put(new[path], leaf.sharding)
    params = _unflatten(new)
    if type(trainer.state.params) is not dict:
        params = type(trainer.state.params)(params)
    trainer.state = trainer.state.replace(params=params)


def optimizer_fields(opt_state, fields) -> dict:
    """field -> tree, for each of ``fields`` that names a field of a named
    tuple anywhere in the optimizer's state (optax: ``mu`` of
    ``ScaleByAdamState``); the first found wins."""
    found = {}

    def visit(node):
        if isinstance(node, tuple) and hasattr(node, "_fields"):
            for field in node._fields:
                if field in fields:
                    found.setdefault(field, getattr(node, field))
        if isinstance(node, (tuple, list)):
            for child in node:
                visit(child)

    visit(opt_state)
    if set(found) != set(fields):
        raise ValueError(f"the optimizer's state has no field "
                         f"{sorted(set(fields) - set(found))}")
    return found


class StepProbe:
    """Stands where the program's step callable stood: ``inner(state, batch)
    -> (state, metrics)`` with ``state.params`` a tree of the leaves ``names``
    maps, ``batch`` a dict of arrays and ``metrics["loss"]`` a scalar.
    ``trainer`` is whatever drives it: its loop leaves at the next batch
    boundary once ``_preempted`` is set. ``state_fields`` names the fields of
    ``state.opt_state`` to copy out after the first step."""

    def __init__(self, trainer, inner, *, open_at, seconds, trace_dir,
                 real_per_step, names, counters, state_fields=()):
        self.epoch_steps = len(real_per_step)
        self.trainer, self.inner = trainer, inner
        self.open_at, self.seconds = open_at, seconds
        self.trace_dir = trace_dir
        self.real_per_step = real_per_step
        self.names = {v: k for k, v in names.items()}  # path -> ref name
        self.counters = counters
        self.state_fields = tuple(state_fields)
        self.steps = 0                  # optimizer steps dispatched so far
        self.check = {"batches": [], "losses": []}
        self.marks = []                 # set-up timeline (name, time)
        self.t_open = self.t_close = None
        self.stamps, self.losses = [], []
        self.examples = 0
        self.compiles_at_open = self.compiles_at_close = None
        self.spans = []                 # (name, start, end) on perf_counter
        self.tracing = False
        self.done = self.synced = False

    # -- reading the first steps (set-up) ---------------------------------

    def _host_params(self, params):
        import jax

        flat = _flatten(dict(jax.device_get(params)))
        return {self.names[p]: np.asarray(v) for p, v in flat.items()}

    def _before_check_step(self, state, batch):
        import jax

        if self.steps == 0:
            self.check["params0"] = self._host_params(state.params)
        self.check["batches"].append(
            {k: np.asarray(v) for k, v in jax.device_get(batch).items()})

    def _after_check_step(self, state, metrics):
        self.check["losses"].append(float(np.asarray(metrics["loss"])))
        if self.steps == 1:
            self.check["params1"] = self._host_params(state.params)
            if self.state_fields:
                self.check["state1"] = {
                    field: self._host_params(tree) for field, tree in
                    optimizer_fields(state.opt_state,
                                     self.state_fields).items()}
        if self.steps == CHECK_STEPS:
            self.check["params3"] = self._host_params(state.params)

    # -- the window -------------------------------------------------------

    def _fence(self, tree):
        import jax

        jax.block_until_ready(tree)

    def _open(self, state):
        import jax

        self._fence(state)
        if self.trace_dir is not None:
            options = jax.profiler.ProfileOptions()
            # device planes only: with the host tracer on, the runtime logs
            # an event for every row it re-tiles on the way to the device
            # (3,100 a step at batch 32), which slowed the host path 3x at
            # batch 32 and 25x at batch 4096 (my chip runs, PR 23)
            options.host_tracer_level = 0
            options.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir, profiler_options=options)
            self.tracing = True
        self.compiles_at_open = self.counters.compilations
        self.t_open = time.perf_counter()

    def _close(self, state):
        import jax

        self._fence(state)
        self.t_close = time.perf_counter()
        self.compiles_at_close = self.counters.compilations
        if self.tracing:
            jax.profiler.stop_trace()
            self.tracing = False
        self.done = True
        # the Trainer's own drain: its loop leaves at the next batch boundary
        self.trainer._preempted = True

    def on_span(self, name, dur_s):
        """The telemetry's spans inside the window; the fence of the step
        that closed it ends just after it and still counts."""
        if self.t_open is None or name not in HOST_SPANS:
            return
        now = time.perf_counter()
        if not self.done or (name == "device_sync" and not self.synced):
            self.synced = self.done
            self.spans.append((name, now - dur_s, now))

    def __call__(self, state, batch):
        checking = self.steps < CHECK_STEPS
        if checking:
            self._before_check_step(state, batch)
        if self.t_open is None and self.steps >= self.open_at:
            self._open(state)
        in_window = self.t_open is not None and not self.done
        t0 = time.perf_counter()
        state, metrics = self.inner(state, batch)
        t1 = time.perf_counter()
        real = self.real_per_step[self.steps % self.epoch_steps]
        self.steps += 1
        if checking:
            self._after_check_step(state, metrics)
            self.marks.append((f"step_{self.steps}", time.perf_counter()))
        if in_window:
            self.stamps.append(t0)
            self.losses.append(metrics["loss"])
            self.examples += real
            if self.trace_dir is not None:
                # the traced slice: bounded, wherever in the epoch it ends
                due = (t1 - self.t_open >= min(self.seconds, TRACE_SECONDS)
                       or len(self.stamps) >= TRACE_DISPATCHES)
            else:
                # the window: whole epochs, the first to end after --seconds;
                # the same work in every run, and the Trainer's own
                # epoch-boundary fence is where it ends
                due = (t1 - self.t_open >= self.seconds
                       and self.steps % self.epoch_steps == 0)
            if due:
                self._close(state)
        return state, metrics


    # -- what the harness reads of the run --------------------------------

    def record(self, ctx, *, chips, shards, marks) -> dict:
        """The run record's keys that come from the probe: the window, the
        check, the set-up timeline (``marks`` are the adapter's own, before
        the first step) and the device's memory peak. The adapter adds what
        only it knows (``trainer_init_s``, ``optimizer``, ...)."""
        import jax

        losses = np.asarray(
            [np.asarray(x) for x in jax.device_get(self.losses)], np.float64)
        peak = 0
        for dev in jax.local_devices()[:chips]:
            stats = dev.memory_stats() or {}
            # in_use counts live arrays only; reserved also holds a running
            # program's temporaries (2.6 GB against 0.39 GB for ResNet-50
            # b256, my chip run, PR 23), so the high-water mark is the larger
            peak = max(peak, int(stats.get("peak_bytes_in_use", 0)),
                       int(stats.get("peak_bytes_reserved", 0)))
        timeline, last = {}, ctx.t_start
        for name, t in marks + self.marks + [("window_open", self.t_open)]:
            timeline[name], last = round(t - last, 3), t
        ctx.say(f"set-up timeline (s, each since the one before): {timeline}")
        return {
            "chips": chips,
            "shards": shards,
            "steps_per_call": 1,
            "setup_s": self.t_open - ctx.t_start,
            "t_open": self.t_open,
            "window_s": self.t_close - self.t_open,
            "examples": self.examples,
            "dispatches": len(self.stamps),
            "steps": len(self.stamps),
            "stamps": self.stamps,
            "nonfinite_steps": int(
                np.size(losses) - np.isfinite(losses).sum()),
            "last_loss": float(losses.reshape(-1)[-1]),
            "compiles_in_window": (self.compiles_at_close
                                   - self.compiles_at_open),
            "memory_peak_bytes": peak,
            "check": self.check,
            "host_spans": self.spans,
            "trace_dir": self.trace_dir,
        }


def run(ctx) -> dict:
    """Drive one cell; returns the run record the harness and the per-layer
    readers read."""
    import jax

    from chipbench.reference import common
    from tpu_ddp.train.trainer import TrainConfig, Trainer

    cfg, traffic = ctx.config, ctx.traffic
    arch = cfg  # the sizes sit at the top level of the configuration file
    seed = datagen.fold_seed(ctx.seed)
    shards = int(traffic["mesh"]["data"])
    marks = [("imports", time.perf_counter())]
    # the set and the weights: the seed's, or the mix's own (``fixed_work``)
    drawn_from = datagen.work_seed(traffic, seed)
    data = ctx.dataset.make(traffic["dataset"], drawn_from)
    marks.append(("dataset", time.perf_counter()))
    ref_params = ctx.reference.init_params(arch, drawn_from)
    datagen.tell_run_seed(ctx.reference, seed)
    marks.append(("weights", time.perf_counter()))

    fields = dict(cfg["train_config"])
    fields.update(traffic.get("overlays", {}))
    fields.update(
        per_shard_batch=int(traffic["per_shard_batch"]),
        steps_per_call=int(traffic.get("steps_per_call", 1)),
        n_devices=int(traffic["chips"]), mesh=dict(traffic["mesh"]),
        seed=seed, epochs=EPOCHS)
    if "num_classes" in arch:
        fields["num_classes"] = int(arch["num_classes"])
    trace_dir = None
    if ctx.trace:
        trace_dir = os.path.join(ctx.scratch_dir, "profile")
        shutil.rmtree(trace_dir, ignore_errors=True)  # one trace, the newest
        fields.update(TRACE_OVERLAY,
                      telemetry_dir=os.path.join(ctx.scratch_dir, "telemetry"))
    t0 = time.perf_counter()
    trainer = Trainer(
        TrainConfig(**fields), train_data=data,
        test_data=tuple(a[:HELD_OUT] for a in data))
    names = ctx.reference.program_names(arch)
    install_weights(trainer, ref_params, names)
    del ref_params
    trainer_init_s = time.perf_counter() - t0
    marks.append(("trainer_init", time.perf_counter()))

    loader = trainer.train_loader
    real = datagen.real_examples_per_step(
        len(data[0]), shards, int(traffic["per_shard_batch"]))
    if len(real) != loader.steps_per_epoch:
        raise RuntimeError(
            f"the loader makes {loader.steps_per_epoch} steps an epoch, the "
            f"sampler arithmetic {len(real)}")
    if trainer.multi_step is not None:
        raise NotImplementedError(
            "steps_per_call > 1: the check reads the state after one "
            "optimizer step, and an epoch mixes fused and single dispatches; "
            "such a mix needs a probe and a check of its own (PERF.md, Open "
            "questions)")
    open_at = getattr(ctx, "open_after_steps", None)
    probe = StepProbe(
        trainer, trainer.train_step, seconds=ctx.seconds,
        open_at=(loader.steps_per_epoch if open_at is None
                 else max(open_at, CHECK_STEPS)),
        trace_dir=trace_dir, real_per_step=real, names=names,
        counters=ctx.counters,
        state_fields=common.task(ctx.reference).OPTIMIZER_STATE)
    trainer.train_step = probe
    if ctx.trace:
        trainer.telemetry.add_span_listener(probe.on_span)
    result = trainer.run()
    if not probe.done:
        raise RuntimeError("Trainer.run came back before the window closed")

    record = probe.record(ctx, chips=int(traffic["chips"]), shards=shards,
                          marks=marks)
    record.update({
        "global_batch": loader.global_batch,
        "trainer_init_s": trainer_init_s,
        "trainer_result": {key: result.get(key) for key in (
            "images_per_sec_per_chip", "mean_step_seconds")},
        "optimizer": common.optimizer_of(fields),
    })
    # free the program's state before the reference runs
    del trainer, probe, result
    return record
