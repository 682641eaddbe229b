"""The controls come out not correct through the harness's own comparison,
at a size a test run holds: the DS reference in the program's place with
its matrix products at a precision below the program's, and the language
model's reference with its weights rounded to a cheaper type."""

import copy

import pytest
import run

DS_NUMBERS = ("rel_err.products", "rel_err.other", "discrete_mismatch")


@pytest.fixture(scope="module")
def ds_readings(tmp_path_factory):
    from conftest import tiny_checkout

    root = tiny_checkout(tmp_path_factory.mktemp("ds"))
    cell = run.load_cell("ds16-paper.closed", root, root / "bench")
    cell.config["batch"]["rows"] = 131072
    return run.execute("ds16-paper.closed", 2**31 + 21, 1.0, False,
                       root=root, bench=root / "bench", platform="cpu",
                       cell=copy.deepcopy(cell), controls=("high", "bf16"))


def test_ds_program_is_correct_beside_the_controls(ds_readings):
    assert ds_readings["correct"] is True


def test_ds_control_is_not_correct(ds_readings):
    """One bfloat16 pass per product (a TPU's default matmul precision)
    fails at least one of the cell's numbers."""
    c = ds_readings["checks"]
    assert any(c[f"control_bf16.{k}"]["value"] > c[k]["limit"]
               for k in DS_NUMBERS)


def test_ds_control_high_reads_above_the_program(ds_readings):
    c = ds_readings["checks"]
    assert (c["control_high.rel_err.products"]["value"]
            > 3 * c["rel_err.products"]["value"])


def test_lm_control_fails_where_the_program_passes(checkout):
    res = run.execute("stablelm-1.6b.chat", 2**31 + 62, 2.0, False,
                      root=checkout, bench=checkout / "bench", platform="cpu",
                      controls=("fp8",))
    assert res["correct"] is True
    c = res["checks"]
    assert c["served_gap"]["value"] <= c["served_gap"]["limit"]
    ctl = c["control_fp8.served_gap"]
    assert ctl["value"] > ctl["limit"]
