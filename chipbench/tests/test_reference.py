"""The plain references against the simulator on the CPU at small sizes:
the program's outputs pass the cell's check, and the references computed
in bfloat16 (the control) fail it."""

import importlib

from chipbench import harness
from chipbench.check import over
from chipbench.tests.conftest import small_cell

SEED = 2 ** 31 + 17          # a seed above 31 bits


def _mode(cell, model):
    mod = importlib.import_module(f"chipbench.modes.{cell.traffic['mode']}")
    return mod.Mode(model, cell.config, cell.traffic,
                    harness.model_seed(SEED))


def _ref(cell):
    return importlib.import_module(
        f"chipbench.reference.{cell.config['reference']}")


def test_program_agrees_with_reference(workload):
    cell = small_cell(workload)
    model = harness.build_model(cell.config, harness.model_seed(SEED))
    mode = _mode(cell, model)
    mode.warm_up()
    for _ in range(2):
        mode.call()
    mode.release()
    numbers, checked, failed = mode.check(_ref(cell), cell.limits)
    assert checked == 2 + cell.traffic.get("warmup_calls", 1)
    assert not over(numbers, cell.limits), numbers
    assert failed == 0
    assert numbers["count_gap"] == 0 and numbers["finite_mismatch"] == 0


def test_bfloat16_control_fails(workload):
    cell = small_cell(workload)
    mode = _mode(cell, None)
    mode.control(_ref(cell), calls=3)
    numbers, checked, failed = mode.check(_ref(cell), cell.limits)
    assert checked == 3
    assert over(numbers, cell.limits), numbers
    assert failed == 3
