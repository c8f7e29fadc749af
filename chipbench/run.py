"""chipbench: one run of one cell of BENCHMARK.json on the chip.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, the one that holds the chips. Everything a cell is made of is
found by the names in ``BENCHMARK.json`` (see ``chipbench/README.md``):

    configs/<config>.json      sizes, precision, how it is run (``adapter``)
    traffic/<mix>.json         batch, dataset, chips, mesh, overlays
    limits/<cell>.json         the limits of ``correct`` and what they came from
    reference/<config>.py      the plain float32 reference, and what it says
                               of its task and its optimizer
    datasets/<kind>.py         the generator of the mix's ``dataset.kind``
    adapters/<kind>.py         how a configuration is driven
    layer_metrics/<metric>.py  the reader of one per-layer metric

The last line of standard output is the result object; everything else
(sample counts, medians, cache traffic) goes on earlier lines; each number
``correct`` compared stands beside its limit under the result's last key and
on the last lines of standard error. A run that cannot measure (no TPU, the wrong
number of chips, a device that is not in ``peaks.json``, a compilation inside
the window, a share above 105%) exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # process start, as near as Python can see it

import argparse
import gc
import importlib.util
import json
import math
import os
import statistics
import sys
import types

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
if REPO not in sys.path:
    sys.path.insert(0, REPO)

SHARE_CEILING = 105.0  # percent; a share above it is a fault, not a number
#: the rate's name, kept from the first cells; it counts the examples trained
#: between the window's fences, whatever a cell's example is
RATE = "images_per_s_per_chip"


class Refused(Exception):
    """The run cannot measure; exit non-zero without a result line."""


def say(*parts):
    print("chipbench:", *parts, flush=True)


def say_compared(*parts):
    """Each number ``correct`` compared, beside its limit: the last lines of
    standard error (nothing is written there after them)."""
    print("chipbench:", *parts, file=sys.stderr, flush=True)


# -- files by name -----------------------------------------------------------

def find(roots, *parts):
    """First ``root/parts...`` that exists. ``roots`` is searched in order;
    shipped files live under ``chipbench/``, a test may put a directory of its
    own in front."""
    for root in roots:
        path = os.path.join(root, *parts)
        if os.path.exists(path):
            return path
    raise Refused(f"no file {os.path.join(*parts)} under {list(roots)}")


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_cell(bench: dict, workload: str, roots, repo=REPO) -> dict:
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise Refused(f"no workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    entry = configs[cell["config"]]
    config_path = os.path.join(repo, entry["file"])
    if not os.path.exists(config_path):  # a test's own configuration
        config_path = find(roots, "configs", os.path.basename(entry["file"]))
    config = load_json(config_path)
    traffic = load_json(find(roots, "traffic", cell["traffic"] + ".json"))
    if int(traffic["chips"]) != int(cell["chips"]):
        raise Refused(f"{workload}: BENCHMARK.json says {cell['chips']} "
                      f"chips, the mix {traffic['chips']}")
    safe = cell["config"].replace("-", "_").replace(".", "_")
    kind = traffic["dataset"]["kind"]
    return {
        "cell": cell, "config": config, "traffic": traffic,
        "dataset": load_module(find(roots, "datasets", kind + ".py"),
                               f"chipbench_dataset_{kind}"),
        "limits": load_json(find(roots, "limits", workload + ".json")),
        "reference": load_module(
            find(roots, "reference",
                 config.get("reference", cell["config"]) + ".py"),
            f"chipbench_reference_{safe}"),
        "adapter": load_module(
            find(roots, "adapters", config["adapter"] + ".py"),
            f"chipbench_adapter_{config['adapter']}"),
    }


def metric_reports_in(metric: dict, workload: str, bench: dict) -> bool:
    """Does this cell report this metric (the contract's ``workloads`` rule)?"""
    if "workloads" in metric:
        return workload in metric["workloads"]
    moves = metric.get("moves")
    if moves is None:
        return True
    target = next(m for m in bench["end_to_end"] if m["name"] == moves)
    return metric_reports_in(target, workload, bench)


# -- the device --------------------------------------------------------------

def check_device(chips: int, peaks: dict) -> dict:
    import jax

    devices = jax.devices()
    first = devices[0]
    if first.platform != "tpu":
        raise Refused(f"no TPU: jax found platform {first.platform!r}")
    if len(devices) != chips:
        raise Refused(f"the cell needs {chips} chips, jax found "
                      f"{len(devices)}")
    if first.device_kind not in peaks:
        raise Refused(f"device kind {first.device_kind!r} is not in "
                      f"peaks.json ({sorted(peaks)})")
    return describe_device(chips)


def describe_device(chips: int) -> dict:
    import jax

    first = jax.devices()[0]
    return {"platform": first.platform, "kind": first.device_kind,
            "count": min(chips, len(jax.devices()))}


def setup_compile_cache():
    """jax's persistent cache at a fixed path inside the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), with the floors at zero so that a
    warm run loads the program's many small set-up programs too."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(REPO, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax.config.jax_compilation_cache_dir


class Counters:
    """Backend compilations and cache traffic, from ``jax.monitoring``."""

    def __init__(self):
        self.compilations = 0
        self.compile_seconds = 0.0
        self.cache = {}

    def install(self):
        from jax import monitoring

        def on_duration(name, seconds, **_):
            if name.endswith("backend_compile_duration"):
                self.compilations += 1
                self.compile_seconds += seconds

        def on_event(name, **_):
            if name.startswith("/jax/compilation_cache/"):
                key = name.rsplit("/", 1)[-1]
                self.cache[key] = self.cache.get(key, 0) + 1

        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)
        return self


# -- reductions --------------------------------------------------------------

def percentile(values, q):
    """Linear-interpolated percentile (numpy's default), without numpy."""
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def end_to_end(record: dict) -> dict:
    k = record["steps_per_call"]
    stamps = record["stamps"]
    intervals = [(b - a) * 1e3 / k for a, b in zip(stamps, stamps[1:])]
    if len(intervals) < 20:
        raise Refused(f"only {len(intervals)} dispatch intervals in the "
                      "window: no 95th percentile")
    say(f"dispatch intervals: n={len(intervals)} "
        f"median_ms={statistics.median(intervals)!r} "
        f"max_ms={max(intervals)!r}")
    return {
        RATE: record["examples"] / record["window_s"] / record["chips"],
        "step_ms_p95": percentile(intervals, 95),
        "setup_s": record["setup_s"],
    }


def program_numbers(task, record) -> dict:
    """Losses, first gradient and three-step update of the timed path, as
    leaf norms, from what the probe read in set-up. The gradient is the one
    the optimizer got, worked out from its state after one step by the
    task's rule (``reference/common.py::first_gradient``), the same rule the
    reference's side is read by."""
    check = record["check"]
    p0 = check["params0"]
    grad = task.first_gradient(record["optimizer"], p0, check["params1"],
                               check.get("state1"))
    update = {k: check["params3"][k].astype("float64") - p0[k] for k in p0}
    return {"losses": check["losses"], "grad": grad, "update": update}


def reference_numbers(loaded, record, precision=None) -> dict:
    """The same numbers from the plain reference, following the same steps
    from the same seeded weights on the same batches as they were fed, by
    the configuration's own loss and optimizer, in float32 at ``highest``
    (``precision``: the control's, one notch below the configuration's)."""
    import numpy as np

    from chipbench.reference import common

    ref, check = loaded["reference"], record["check"]
    task = common.task(ref)
    followed = task.follow(
        loaded["config"], check, shards=record["shards"],
        optimizer=record["optimizer"],
        precision=precision or "float32_highest")
    p0 = check["params0"]
    update = {k: np.asarray(followed["params"][k], "float64") - p0[k]
              for k in p0}
    grad = task.first_gradient(
        record["optimizer"], p0, followed["params_after_first"],
        followed.get("state_after_first"))
    return {"losses": followed["losses"], "grad": grad, "update": update,
            "output_leaves": ref.OUTPUT_LEAVES}


def gaps(numbers: dict, ref_numbers: dict) -> dict:
    from chipbench import compare

    return compare.readings(numbers, ref_numbers)


def repeated_rows(task, record) -> int:
    """Rows of the checked steps that repeat an earlier one; 0 is sound."""
    rows = [row.tobytes() for b in record["check"]["batches"]
            for row in task.rows(b)]
    say(f"check: {len(record['check']['batches'])} steps, {len(rows)} rows, "
        f"{len(set(rows))} distinct")
    return len(rows) - len(set(rows))


def decide_correct(loaded, record):
    """Follow the timed path's first three steps with the plain reference
    and compare (``chipbench/compare.py``). Runs after the window has closed
    and the program's state is freed; not part of ``setup_s``. Returns the
    verdict and each number compared beside its limit."""
    from chipbench import compare
    from chipbench.reference import common

    t0 = time.perf_counter()
    task = common.task(loaded["reference"])
    repeated = repeated_rows(task, record)
    compared = {"repeated_rows": {"value": repeated, "limit": 0}}
    say_compared(f"correct: repeated_rows = {repeated} limit 0 "
                 f"{'FAILED' if repeated else 'ok'}")
    if repeated:
        return False, compared
    program = program_numbers(task, record)
    reference = reference_numbers(loaded, record)
    say(f"check: losses {program['losses']!r} reference "
        f"{reference['losses']!r} ({time.perf_counter() - t0:.2f} s)")
    read = gaps(program, reference)
    limits = loaded["limits"]["limits"]
    # json has no inf or nan: a reading that is not finite goes as text
    compared.update({
        name: {"value": value if math.isfinite(value) else repr(value),
               "limit": limits[name]}
        for name, (value, _) in read.items()})
    return compare.decide(read, limits, out=say_compared), compared


def per_layer(bench, workload, roots, record, reduced) -> dict:
    """The cell's per-layer metrics, each by its own reader; ``run.record``
    is the adapter's run record, ``run.trace`` the reduced trace."""
    run_view = types.SimpleNamespace(record=record, trace=reduced)
    out = {}
    for metric in bench["per_layer"]:
        if not metric_reports_in(metric, workload, bench):
            continue
        reader = load_module(
            find(roots, "layer_metrics", metric["name"] + ".py"),
            "chipbench_metric_" + metric["name"].replace(".", "_"))
        value = reader.read(run_view)
        if value is None:
            continue
        if metric["unit"] == "%" and value > SHARE_CEILING:
            raise Refused(f"{metric['name']} reads {value!r}%: the work is "
                          "counted too high or the time leaves part out")
        out[metric["name"]] = {"value": value, "unit": metric["unit"]}
    return out


#: HLO's own names for the instructions that run a branch or a body
#: (``conditional.2``, ``while``, ``call.7``): a trace shows one as long as
#: what it runs and shows those operations beside it, so its time is theirs.
#: They are what the program's map calls ``control`` (``scopes.CONTROL``)
CONTROL_STEMS = ("conditional", "while", "call")


def breakdown(reduced) -> dict:
    """What the ledger keeps of a traced run, ten entries of each at most.
    ``device_ops``: the largest operations of chip 0, each as its share of
    the busy time that ``device_step_ms`` is made of (the union of the ``XLA
    Ops`` intervals over the slice's steps), every instruction once: one
    named as control flow (``CONTROL_STEMS``) is left out, because the
    branch's or body's operations are in the list. ``idle_gaps``: seconds of
    the traced slice (``device.window_s``) in which chip 0 ran nothing, by
    what the host was doing."""
    busy_s = reduced["steps"] * reduced["device_step_ms"] / 1e3
    shares = [[name, seconds / busy_s]
              for name, seconds in reduced["device_ops"]
              if name.split(".")[0] not in CONTROL_STEMS]
    return {"device_ops": shares[:10], "idle_gaps": reduced["idle_gaps"][:10]}


# -- one run -----------------------------------------------------------------

def run_cell(workload, seed, seconds, trace, *, bench_path=None, roots=None,
             device_check=True):
    """Returns the result object (a dict). Raises ``Refused`` where the run
    cannot measure."""
    roots = list(roots or []) + [HERE]
    bench = load_json(bench_path or os.path.join(REPO, "BENCHMARK.json"))
    loaded = load_cell(bench, workload, roots)
    chips = int(loaded["cell"]["chips"])
    peaks = load_json(find(roots, "peaks.json"))
    cache_dir = setup_compile_cache()
    counters = Counters().install()
    device = check_device(chips, peaks) if device_check else (
        describe_device(chips))
    say(f"{workload} seed={seed} seconds={seconds} trace={int(trace)} "
        f"device={device} cache={cache_dir}")

    scratch = os.path.join(REPO, ".chipbench_runs", workload)
    os.makedirs(scratch, exist_ok=True)
    ctx = types.SimpleNamespace(
        cell=loaded["cell"], config=loaded["config"],
        traffic=loaded["traffic"], reference=loaded["reference"],
        dataset=loaded["dataset"], seed=seed, seconds=seconds, trace=trace,
        counters=counters, scratch_dir=scratch, t_start=T_START, say=say)
    record = loaded["adapter"].run(ctx)
    gc.collect()
    record["compile_s"] = counters.compile_seconds
    say(f"set-up: compilations={counters.compilations} "
        f"compile_s={counters.compile_seconds!r} cache={counters.cache} "
        f"trainer_init_s={record['trainer_init_s']!r}")
    say(f"window: {record['window_s']!r} s, {record['steps']} steps, "
        f"{record['examples']} examples, last loss {record['last_loss']!r}, "
        f"the program's own figures {record['trainer_result']}")
    if record["compiles_in_window"]:
        raise Refused(f"{record['compiles_in_window']} compilations inside "
                      "the window: not steady state")

    device["memory_peak_bytes"] = record["memory_peak_bytes"]
    result = {"correct": None, "attempted": record["steps"],
              "failed": record["nonfinite_steps"]}
    if trace:
        from chipbench import xplane

        try:
            reduced = xplane.reduce_run(record, say=say)
        except (FileNotFoundError, ValueError) as e:
            raise Refused(f"the traced slice cannot be reduced: {e}")
        record["peak_flops_per_s"] = peaks.get(device["kind"], {}).get(
            "bf16_flops_per_s")
        record["train_flops_per_example"] = train_flops_per_example(loaded)
        result["metrics"] = per_layer(bench, workload, roots, record, reduced)
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        idle = 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])
        say(f"traced slice: {reduced['steps']} steps in "
            f"{reduced['window_s']!r} s, device idle {idle!r}%, the slice's "
            f"own rate {reduced['examples_per_s_per_chip']!r} "
            "examples/s/chip")
        if not -5.0 <= idle <= 100.0:
            raise Refused(f"idle share {idle!r}% is not a share")
        result["breakdown"] = breakdown(reduced)
    else:
        values = end_to_end(record)
        # an example that is a sequence: its mix states what it holds, and
        # the rate in those units is printed beside the rate in examples
        for unit, n in loaded["traffic"]["dataset"].get(
                "example_holds", {}).items():
            say(f"{unit}_per_s_per_chip={values[RATE] * n!r} "
                f"({n} {unit} an example)")
        units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
        result["metrics"] = {
            name: {"value": values[name], "unit": units[name]}
            for name in values
            if metric_reports_in(
                next(m for m in bench["end_to_end"] if m["name"] == name),
                workload, bench)}
    ok, compared = decide_correct(loaded, record)
    result["correct"] = bool(ok and record["nonfinite_steps"] == 0)
    say_compared(f"correct = {result['correct']} "
                 f"({record['nonfinite_steps']} steps with a loss not finite)")
    result["device"] = device
    # each number compared beside its limit: last in the line
    result["compared"] = compared
    return result


def train_flops_per_example(loaded) -> float:
    """Required FLOPs of training on one example of this cell, from shapes,
    by the configuration's own count where its reference file gives one."""
    from chipbench.reference import common

    flops = float(common.task(loaded["reference"]).train_flops_per_example(
        loaded["config"], loaded["traffic"]))
    say(f"train_flops_per_example={flops!r}")
    return flops


def main(argv=None, **kwargs) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = parser.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), **kwargs)
    except Refused as e:
        print(f"chipbench: refused: {e}", file=sys.stderr, flush=True)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
