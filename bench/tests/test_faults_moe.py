"""A whole run of the MoE LM cell on the CPU, at a tiny size, past the
harness's look for a chip: ``correct`` holds for the sound run and comes
out false for the reference's control in the program's place and for
each fault planted in the timed path."""
import pytest

from bench import harness
from bench.tests import faults, tiny_moe

SEED = 2 ** 31 + 13


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_moe.make_root(str(tmp_path_factory.mktemp("moe")))


def run(root, plant=None):
    c = harness.Cell(tiny_moe.CELL, root)
    obj = None
    if plant is not None:
        obj = plant(c.kind_module().build_objective(c.config, c.traffic))
    return harness.run_cell(tiny_moe.CELL, SEED, 0.5, False, root=root,
                            platform="cpu", objective=obj, log=lambda s: 0)


def test_sound_run_is_correct(root):
    res = run(root)
    assert res["correct"], res["compared"]
    assert set(res["metrics"]) == {"tokens_per_s", "peak_hbm_gib",
                                   "setup_s"}


@pytest.mark.parametrize("plant", [faults.state_unchanged,
                                   faults.lm_half_batch,
                                   faults.lm_loss_altered],
                         ids=["state_unchanged", "half_batch",
                              "loss_altered"])
def test_fault_is_not_correct(root, plant):
    res = run(root, plant)
    assert not res["correct"], res["compared"]


def test_control_is_not_correct(root):
    """The reference one precision below the configuration's, in the
    program's place, fails at least one of the cell's limits, where the
    program passes them all."""
    cell = harness.Cell(tiny_moe.CELL, root)
    prog, hparams = harness.first_steps(tiny_moe.CELL, SEED, root=root,
                                        platform="cpu")
    refs = harness.reference_readings(cell, SEED, hparams)
    values = harness.compare(prog, refs)
    assert all(values[k] <= lim for k, lim in cell.limits.items()
               if k in values)
    control = harness.reference_readings(cell, SEED, hparams, "control")
    values = harness.compare(harness.as_program(control), refs)
    assert any(values[k] > lim for k, lim in cell.limits.items()
               if k in values), values


def test_the_kind_checks_the_file_against_the_program(root):
    """A field the program does not run as the file states is refused."""
    cell = harness.Cell(tiny_moe.CELL, root)
    kind = cell.kind_module()
    cfg = kind.model_config(cell.config)
    assert (cfg.n_experts, cfg.n_held, cfg.expert_offset) == (16, 4, 4)
    assert cfg.first_dense == 1 and cfg.n_repeat == 2
    for key, value in (("scoring_func", "softmax"), ("n_group", 8),
                       ("q_lora_rank", 1536), ("topk_method", "greedy")):
        with pytest.raises(ValueError):
            kind.model_config(dict(cell.config, **{key: value}))
