"""A whole run of an LM cell on the CPU, past the harness's look for a
chip, with the timed path sound and then broken underneath: ``correct``
holds for the sound run and comes out false for each fault the cell can
have (one chip: no exchange between chips)."""
import pytest

from bench import harness
from bench.tests import faults, tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("lm")))


def run(root, plant=None):
    c = harness.Cell("lm.tiny", root)
    obj = None
    if plant is not None:
        obj = plant(c.kind_module().build_objective(c.config, c.traffic))
    return harness.run_cell("lm.tiny", 2 ** 31 + 13, 0.5, False, root=root,
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
    program's place, fails at least one of the cell's limits."""
    cell = harness.Cell("lm.tiny", root)
    seed = 2 ** 31 + 13
    prog, hparams = harness.first_steps("lm.tiny", seed, root=root,
                                        platform="cpu")
    refs = harness.reference_readings(cell, seed, hparams)
    values = harness.compare(prog, refs)
    assert all(values[k] <= lim for k, lim in cell.limits.items()
               if k in values)
    control = harness.reference_readings(cell, seed, hparams, "control")
    values = harness.compare(harness.as_program(control), refs)
    assert any(values[k] > lim for k, lim in cell.limits.items()
               if k in values), values
