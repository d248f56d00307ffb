"""The control fails the check at a size a test run can hold: the plain
reference computed in fixed-4, the precision below the configuration's
fixed-8, in the program's place."""
import check
from conftest import TINY_CONFIG, tiny_layers, tiny_traffic
from control import control_numbers


def test_control_is_not_correct():
    _prog, host = tiny_layers(3)
    numbers = control_numbers(host, TINY_CONFIG, tiny_traffic())
    assert not check.passed(numbers)
    assert numbers["bt_rows_differing"]["value"] == 3
    assert numbers["bt_max_abs_diff"]["value"] > 0
