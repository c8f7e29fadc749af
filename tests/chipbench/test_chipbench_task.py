"""The harness knows no task and no optimizer: a configuration of another
task (token sequences, next-token loss over real targets) under another
optimizer (AdamW) is files only. ``chipbench_tiny_lm`` holds such a benchmark;
the shipped ``run_cell`` and ``control.py`` run it, pass the sound program,
fail the timed step broken four ways, and divide ``step_mfu`` by the FLOPs
the configuration's own reference file counts. The defaults of the seam are
what the first cells had: an image classifier under SGD."""

import json
import os
import sys
import types

import jax
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)
sys.path.insert(0, HERE)

import chipbench_tiny_lm as tiny_lm  # noqa: E402
from chipbench import control  # noqa: E402
from chipbench import run as harness  # noqa: E402
from chipbench.adapters import trainer as trainer_adapter  # noqa: E402
from chipbench.reference import common  # noqa: E402

_CONFIG = ("jax_compilation_cache_dir",
           "jax_persistent_cache_min_compile_time_secs",
           "jax_persistent_cache_min_entry_size_bytes")


@pytest.fixture(autouse=True)
def keep_jax_config():
    """The harness points jax's cache at the checkout; put it back."""
    saved = {k: getattr(jax.config, k) for k in _CONFIG}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def run_lm(tmp_path, cell=tiny_lm.CELL, seed=5, **kwargs):
    bench, roots = tiny_lm.write(str(tmp_path), **kwargs)
    # 1.0 s: the harness wants 20 dispatch intervals, so three of these
    # 8-step epochs, so 16 steps inside ``--seconds``: a CPU step of up to
    # 62.5 ms (0.3 s held up to 18.75 ms; test_chipbench_laguna.py::SECONDS)
    return harness.run_cell(cell, seed, 1.0, False, bench_path=bench,
                            roots=roots, device_check=False)


def load_lm(tmp_path, **kwargs):
    bench, roots = tiny_lm.write(str(tmp_path), **kwargs)
    return harness.load_cell(harness.load_json(bench), tiny_lm.CELL,
                             roots + [harness.HERE]), bench, roots


def test_a_token_cell_under_adamw_is_new_files_only_and_correct(tmp_path,
                                                                capsys):
    result = run_lm(tmp_path, seed=2**31 + 13)
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] > 20
    with open(tmp_path / "configs" / "tiny-lm.json") as f:
        config = json.load(f)
    assert "image_size" not in config and "num_classes" not in config
    assert config["train_config"]["optimizer"] == "adamw"
    # each number compared beside its limit, last in the line
    assert list(result)[-1] == "compared"
    assert set(result["compared"]) == {
        "repeated_rows", "loss_gap", "grad_gap", "update_gap", "grad_diff",
        "out_grad_diff"}
    for number in result["compared"].values():
        assert 0 <= number["value"] <= number["limit"]
    json.loads(json.dumps(result))
    # the rate counts examples; a mix whose example is a sequence says what
    # it holds and the rate in those units is printed beside it
    printed = capsys.readouterr().out
    rate = result["metrics"]["images_per_s_per_chip"]["value"]
    line = [ln for ln in printed.splitlines()
            if ln.startswith("chipbench: tokens_per_s_per_chip=")][-1]
    assert float(line.split("=")[1].split()[0]) == pytest.approx(16 * rate)


def test_the_programs_own_lm_step_agrees_with_the_plain_reference(tmp_path):
    result = run_lm(tmp_path, cell=tiny_lm.PRODUCT_CELL, seed=6)
    assert result["correct"] is True
    assert result["compared"]["loss_gap"]["value"] < 1e-5


@pytest.mark.parametrize("fault,caught_by", [
    ("shift_left_out", "loss_gap"),
    ("padding_counted", "loss_gap"),
    ("second_moment_left_out", "update_gap"),
    ("leaf_unchanged", "update_gap"),
])
def test_a_token_step_broken_underneath_is_not_correct(tmp_path, fault,
                                                       caught_by):
    result = run_lm(tmp_path, fault=fault)
    assert result["correct"] is False
    number = result["compared"][caught_by]
    assert number["value"] > 3 * number["limit"]


def test_adams_first_gradient_is_read_from_its_first_moment(tmp_path):
    """Without its second moment Adam's step is wrong and its first moment is
    right: the gradient readings stay at float32 noise, which ``(p0 - p1) /
    lr`` (the gradient's sign under Adam) could not show."""
    result = run_lm(tmp_path, fault="second_moment_left_out")
    assert result["compared"]["grad_diff"]["value"] < 1e-5
    assert result["compared"]["out_grad_diff"]["value"] < 1e-5
    assert result["compared"]["update_gap"]["value"] > 0.9


def test_step_mfu_divides_the_reference_files_own_flops(tmp_path):
    loaded, bench, roots = load_lm(tmp_path)
    arch, traffic = loaded["config"], loaded["traffic"]
    flops = harness.train_flops_per_example(loaded)
    per_token = 2 * 12 * 32 * 32 + 32 * 64
    assert flops == 6.0 * (16 * per_token + 2 * 2 * 32 * 16 * 17 // 2)
    assert flops == loaded["reference"].train_flops_per_example(arch, traffic)
    reader = harness.load_module(
        os.path.join(harness.HERE, "layer_metrics", "step_mfu.py"), "mfu")

    def mfu(per_example):
        record = {"examples": 80, "steps": 10, "chips": 1,
                  "peak_flops_per_s": 1e9,
                  "train_flops_per_example": per_example}
        return reader.read(types.SimpleNamespace(
            record=record, trace={"device_step_ms": 4.0}))

    assert mfu(flops) == pytest.approx(100 * 8 * flops / 4e-3 / 1e9)
    # a reference file whose function returns half reads half
    path = os.path.join(roots[0], "reference", "tiny-lm.py")
    with open(path) as f:
        text = f.read()
    with open(path, "w") as f:
        f.write(text.replace("    return 3.0 * 2.0 * (", "    return 3.0 * ("))
    halved = harness.load_cell(harness.load_json(bench), tiny_lm.CELL,
                               roots + [harness.HERE])
    assert harness.train_flops_per_example(halved) == flops / 2
    assert mfu(harness.train_flops_per_example(halved)) == pytest.approx(
        mfu(flops) / 2)


def test_the_control_reads_a_token_cell_through_the_same_seam(tmp_path,
                                                              capsys):
    bench, roots = tiny_lm.write(str(tmp_path))
    control.main(["--workload", tiny_lm.CELL, "--seeds", "1,2,3"],
                 roots=roots, bench_path=bench, device_check=False)
    line = [ln for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("control summary:")][-1]
    summary = json.loads(line.split(":", 1)[1])
    assert summary["control_precision"] == "bfloat16"
    for name in ("grad_diff", "out_grad_diff"):
        assert summary[name + ".control_min"] > 3 * summary[
            name + ".sound_max"]


def test_the_control_alone_lays_batches_out_as_the_task_does(tmp_path,
                                                             capsys):
    loaded, bench, roots = load_lm(tmp_path)
    record = control.seeded_record(loaded, 4)
    assert record["optimizer"] == {"name": "adamw", "lr": 0.001,
                                   "momentum": 0.0, "weight_decay": 0.01}
    assert len(record["check"]["batches"]) == trainer_adapter.CHECK_STEPS
    for batch in record["check"]["batches"]:
        assert set(batch) == {"tokens", "mask"}
        assert batch["tokens"].shape == (8, 16)
        assert batch["tokens"].dtype == np.int32
    control.main(["--workload", tiny_lm.CELL, "--seeds", "4", "--read",
                  "control"], roots=roots, bench_path=bench,
                 device_check=False)
    row = [json.loads(ln.split(":", 1)[1])
           for ln in capsys.readouterr().out.splitlines()
           if ln.startswith("control:")][-1]
    assert "sound" not in row and row["control"]["out_grad_diff"] > 1e-3


def _control_row(tmp_path, capsys, *more):
    _, bench, roots = load_lm(tmp_path)
    control.main(["--workload", tiny_lm.CELL, "--seeds", "4", "--read",
                  "control", *more], roots=roots, bench_path=bench,
                 device_check=False)
    lines = capsys.readouterr().out.splitlines()
    row, summary = (json.loads(
        [ln for ln in lines if ln.startswith(head)][-1].split(":", 1)[1])
        for head in ("control:", "control summary:"))
    return row, summary


def test_the_control_at_a_precision_of_choice_reads_under_the_notch_below(
        tmp_path, capsys):
    """``--precision`` puts the reference in the program's place at the
    precision named: the stated one (float32 here; bfloat16 in the decoder
    cells, where it says what that precision alone makes of a reading) reads
    under the notch below it in ``out_grad_diff``, and that under the next."""
    read = {}
    for precision in ("float32_highest", "bfloat16", "float8"):
        row, summary = _control_row(
            tmp_path / precision, capsys, "--precision", precision)
        assert summary["control_precision"] == precision
        assert row["control_precision"] == precision
        read[precision] = row["control"]["out_grad_diff"]
    assert read["float32_highest"] < read["bfloat16"] / 3
    assert read["bfloat16"] < read["float8"] / 3


def test_a_reference_file_that_says_nothing_is_an_image_classifier_under_sgd(
        case, bench):
    loaded = harness.load_cell(bench, "resnet50-cifar.b512", case.roots)
    ref = loaded["reference"]
    for name in ("follow", "first_gradient", "rows", "batches",
                 "train_flops_per_example", "OPTIMIZER_STATE"):
        assert not hasattr(ref, name)
    task = common.task(ref)
    assert task.OPTIMIZER_STATE == ()
    assert task.first_gradient is common.first_gradient
    batch = {"image": np.zeros((2, 3)), "label": np.zeros(2)}
    assert task.rows(batch) is batch["image"]
    sgd = common.optimizer_of(loaded["config"]["train_config"])
    assert sgd == {"name": "sgd", "lr": 0.02, "momentum": 0.9,
                   "weight_decay": 0.0}
    p0 = {"w": np.float32([1.0, 2.0])}
    p1 = {"w": np.float32([0.5, 2.5])}
    grad = task.first_gradient(sgd, p0, p1)
    assert grad["w"].dtype == np.float64
    assert np.array_equal(grad["w"], np.float64([0.5, -0.5]) / 0.02)
    # the shipped cells' required FLOPs, as PR 23 counted them
    assert task.train_flops_per_example(
        loaded["config"], loaded["traffic"]) == pytest.approx(
            7.787e9, abs=1e6)


@pytest.mark.parametrize("name", ["follow", "first_gradient"])
def test_the_defaults_refuse_an_optimizer_they_do_not_follow(name):
    adamw = {"name": "adamw", "lr": 1e-3, "momentum": 0.0,
             "weight_decay": 0.0}
    task = common.task(types.SimpleNamespace())
    with pytest.raises(ValueError, match="reference file gives its own"):
        if name == "follow":
            task.follow({}, {"batches": []}, shards=1, optimizer=adamw,
                        precision="float32_highest")
        else:
            task.first_gradient(adamw, {}, {})


def test_the_probe_finds_the_optimizer_state_by_field_name():
    import jax.numpy as jnp
    import optax

    params = {"a": {"kernel": jnp.ones((2, 2))}, "b": jnp.ones(3)}
    tx = optax.adamw(1e-3, weight_decay=0.1)
    grads = jax.tree.map(lambda p: 0.5 * p, params)
    _, state = tx.update(grads, tx.init(params), params)
    found = trainer_adapter.optimizer_fields(state, ("mu",))
    assert set(found) == {"mu"}
    assert np.allclose(found["mu"]["a"]["kernel"], 0.05)  # (1 - 0.9) * g
    with pytest.raises(ValueError, match="velocity"):
        trainer_adapter.optimizer_fields(state, ("velocity",))


def test_a_dataset_of_another_kind_is_a_file_found_by_its_name(tmp_path):
    loaded, bench, roots = load_lm(tmp_path)
    make = loaded["dataset"].make
    assert loaded["traffic"]["dataset"]["kind"] == "token_sequences"
    spec = {"kind": "token_sequences", "size": 40, "seq_len": 16,
            "vocab_size": 64, "min_len": 9}
    tokens, mask = make(spec, 2**31 + 5)
    again, _ = make(spec, 2**31 + 5)
    other, _ = make(spec, 2**31 + 6)
    assert np.array_equal(tokens, again) and not np.array_equal(tokens, other)
    assert tokens.dtype == np.int32 and tokens.shape == (40, 16)
    assert mask.dtype == bool and mask[:, :9].all() and not mask.all()
    assert not tokens[~mask].any()
    assert len({row.tobytes() for row in tokens}) == 40
    with pytest.raises(harness.Refused, match="datasets/token_sequences.py"):
        # not beside the shipped generators
        harness.find([harness.HERE], "datasets", "token_sequences.py")


def test_run_py_names_no_task_and_no_optimizer():
    """What it needs of either it asks of the reference file or finds in the
    adapter's record; the rate keeps the name the first cells gave it."""
    with open(os.path.join(harness.HERE, "run.py")) as f:
        text = f.read().lower().replace('"images_per_s_per_chip"', "")
    for word in ("image", "label", "num_classes", "sgd", "momentum", "adam",
                 "token"):
        assert word not in text, word
