"""The control comes out not correct: the reference in fp8 put in the
program's place, at a size a test run holds (the cells' configurations with
tiny widths, in the bf16 they state), read by the same comparison as the
program's own served tokens, under the configuration's own limit.

At the cells' own sizes the control runs on the chip
(``readings.py``; PERF.md gives its readings)."""
import pytest

from conftest import tiny_cell


@pytest.mark.parametrize("config,traffic", [
    ("mistral-nemo-12b-l8", "chat"), ("codestral-22b-l8", "code")])
@pytest.mark.parametrize("seed", [11, 2 ** 31 + 7])
def test_control_fails_where_the_program_passes(config, traffic, seed):
    cell = tiny_cell(config, traffic, seed=seed, n_requests=16)
    cell.conf["torch_dtype"] = "bfloat16"
    cell.control = True
    out = cell.driver.run(cell)
    gap = out.checks[0]
    assert gap.name == "max_logit_gap"
    assert gap.ok, gap
    assert out.info["control_max_logit_gap"] > gap.limit, out.info
